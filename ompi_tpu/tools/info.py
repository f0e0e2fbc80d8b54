"""ompi_tpu_info — introspection CLI.

Reference: ompi/tools/ompi_info — dumps every framework, component, and
MCA parameter so users can see exactly what the library will select and
which knobs exist. Usage:

    python -m ompi_tpu.tools.info                 # everything, level <= 6
    python -m ompi_tpu.tools.info --level 9       # developer params too
    python -m ompi_tpu.tools.info --param btl     # one framework's vars
    python -m ompi_tpu.tools.info --pvars         # performance variables
"""

from __future__ import annotations

import argparse
import sys


def _load_everything() -> None:
    """Import every component module so registries are populated (the
    CLI analog of the reference's component-repository scan —
    mca_base_component_repository.c:365)."""
    import ompi_tpu.runtime.state  # btl/coll component side effects
    import ompi_tpu.accelerator  # accelerator framework
    import ompi_tpu.coll.xla  # mesh collectives
    import ompi_tpu.coll.neighbor  # topology collectives
    import ompi_tpu.runtime.spc  # spc vars
    import ompi_tpu.runtime.trace  # trace cvars + pvars
    import ompi_tpu.runtime.metrics  # metrics cvars + straggler/critpath pvars (metrics_critpath_steps/bound_rank/bound_category)
    import ompi_tpu.runtime.sanitizer  # sanitizer cvars + pvar
    import ompi_tpu.pml.monitoring  # pml_monitoring enable cvar
    import ompi_tpu.runtime.topology  # topo binding vars
    import ompi_tpu.pml.ob1  # pml vars
    import ompi_tpu.pml.vprotocol  # pml_v message-logging vars
    import ompi_tpu.runtime.smsc  # single-copy (cma) vars
    import ompi_tpu.io.file  # collective-IO aggregator vars
    import ompi_tpu.ft.era  # agreement vars
    import ompi_tpu.ft.detector  # heartbeat detector vars
    import ompi_tpu.ft.inject  # chaos-plan vars + injected-faults pvar
    import ompi_tpu.ft.recovery  # failover/retry/respawn pvars
    import ompi_tpu.ft.diskless  # diskless ckpt cvars + ft_ckpt_* pvars
    import ompi_tpu.runtime.dpm  # dynamic-process spawn vars
    import ompi_tpu.reshard.plan  # reshard cvars + plans_compiled pvar
    import ompi_tpu.reshard.exec  # reshard exec/bytes/staging pvars
    import ompi_tpu.quant  # quant_* cvars + colls/bytes pvars
    import ompi_tpu.quant.negotiate  # negotiation topics
    import ompi_tpu.coll.quant  # quantized-collectives component
    import ompi_tpu.coll.hier.compose  # hier composer + coll_hier cvars
    import ompi_tpu.coll.hier  # hier_plan_hits/misses/retunes pvars
    import ompi_tpu.btl.tcp  # btl_tcp compress/writev + reliable/retx_*/link_* cvars, datapath + link pvars
    import ompi_tpu.runtime.progress  # idle-block cvar + progress_idle_blocks pvar
    import ompi_tpu.runtime.mpool  # BufferPool mpool_pool_* pvars
    import ompi_tpu.coll.sched  # coll_round_window cvar + datapath pvars
    import ompi_tpu.coll.persist  # coll_persist_* cvars + persist_* replay pvars
    import ompi_tpu.qos  # QoS classes: btl_tcp_shape_enable/segment + qos_* cvars/pvars
    import ompi_tpu.runtime.forensics  # stall-forensics cvars + forensics_* pvars
    import ompi_tpu.runtime.linkmodel  # fabric telemetry: linkmodel_* cvars + rtt/goodput/probe pvars
    import ompi_tpu.serve  # elastic serving: serve_* SLO/RTO/admission cvars + pvars
    # (btl/tcp.py above also carries the btl_tcp_shape_* scheduler knobs)
    # mpilint/mpiracer/mpiown (ompi_tpu/analysis/) are build-time gates
    # by design: they register no cvars/pvars, so there is nothing to
    # load


def print_header(out) -> None:
    from ompi_tpu.version import __version__

    print(f"ompi_tpu: {__version__}", file=out)
    print(f"python:   {sys.version.split()[0]}", file=out)
    try:
        import jax

        print(f"jax:      {jax.__version__}", file=out)
    except Exception:
        print("jax:      unavailable", file=out)


def print_components(out) -> None:
    from ompi_tpu.mca.component import all_frameworks

    print("\nframeworks / components "
          "(reference: ompi_info component list):", file=out)
    for fname, fw in sorted(all_frameworks().items()):
        comps = sorted(fw.components.values(),
                       key=lambda c: -c.PRIORITY)
        names = ", ".join(f"{c.NAME} (priority {c.PRIORITY})"
                          for c in comps) or "-"
        print(f"  {fname:<14} {fw.description}", file=out)
        print(f"  {'':<14} components: {names}", file=out)


def print_vars(out, level: int, framework: str = "") -> None:
    from ompi_tpu.mca.var import all_vars

    print(f"\nmca parameters (level <= {level}"
          + (f", framework '{framework}'" if framework else "") + "):",
          file=out)
    for key, var in sorted(all_vars().items()):
        if var.level > level:
            continue
        if framework and var.framework != framework:
            continue
        src = var.source.name.lower()
        print(f"  {var.full_name:<36} = {var.value!r:<14} "
              f"[{var.typ.__name__}, level {var.level}, source {src}]",
              file=out)
        if var.help:
            print(f"  {'':<36}   {var.help}", file=out)


def print_pvars(out) -> None:
    from ompi_tpu.mca.var import all_pvars

    print("\nperformance variables (reference: MPI_T pvars / "
          "mca_base_pvar.c):", file=out)
    pvars = all_pvars()
    if not pvars:
        print("  (none recorded yet)", file=out)
    for key, pv in sorted(pvars.items()):
        print(f"  {pv.full_name:<36} = {pv.value!r}", file=out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="ompi_tpu_info",
        description="Dump frameworks, components, and MCA parameters")
    ap.add_argument("--level", type=int, default=6,
                    help="max parameter level to show (1-9, default 6)")
    ap.add_argument("--param", default="",
                    help="restrict parameters to one framework")
    ap.add_argument("--pvars", action="store_true",
                    help="show performance variables")
    ap.add_argument("--all", action="store_true",
                    help="everything incl. level-9 params and pvars")
    opts = ap.parse_args(argv)
    if opts.all:
        opts.level, opts.pvars = 9, True

    _load_everything()
    out = sys.stdout
    print_header(out)
    print_components(out)
    print_vars(out, opts.level, opts.param)
    if opts.pvars:
        print_pvars(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
