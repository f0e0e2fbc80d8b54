"""mpirun — process-mode launcher.

Reference: ompi/tools/mpirun/main.c (a thin wrapper handing off to PRRTE's
prterun) + the prted PMIx server it relies on. Here the launcher hosts the
modex server itself (no external runtime dependency) and spawns one Python
process per rank with the launch-contract env:

    OMPI_TPU_RANK, OMPI_TPU_SIZE, OMPI_TPU_MODEX

Multi-host jobs (reference: prte's plm/ssh daemon launch): ``--hostfile``
or ``--host`` place ranks onto nodes; remote ranks are started through a
pluggable launch agent (``--launch-agent``, default ssh — the
plm_ssh_agent analog; ``fake`` is the in-tree CI shim) with the launch
contract marshalled into the remote command line, and the modex server
listens on all interfaces advertising its best non-loopback address.

Usage:
    python -m ompi_tpu.tools.mpirun -np 4 [--mca k v]... script.py [args...]
    python -m ompi_tpu.tools.mpirun -np 4 --host n1:2,n2:2 script.py
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
from typing import List, Optional

from ompi_tpu.runtime import plm
from ompi_tpu.runtime.modex import ModexServer


def main(argv: List[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="mpirun (ompi_tpu)")
    parser.add_argument("-np", "-n", type=int, required=True, dest="np",
                        help="number of ranks")
    parser.add_argument("--mca", nargs=2, action="append", default=[],
                        metavar=("VAR", "VALUE"),
                        help="set an MCA variable (framework_name value)")
    parser.add_argument("--timeout", type=float, default=600.0,
                        help="job wall-clock limit in seconds")
    parser.add_argument("--hostfile", "--machinefile", default=None,
                        help="hostfile: one 'node [slots=N]' per line")
    parser.add_argument("--host", "-H", default=None,
                        help="inline host list: n1[:slots],n2[:slots]")
    parser.add_argument("--launch-agent", default="ssh",
                        help="remote-exec agent for non-local hosts "
                             "(argv contract: AGENT HOST COMMAND; 'fake' "
                             "= in-tree local shim for CI)")
    parser.add_argument("--with-tpu", action="store_true",
                        help="let the rank claim the TPU (one rank only: "
                             "a chip belongs to one process; default: "
                             "ranks are host-only, the device path belongs "
                             "to mesh mode / the single controller)")
    parser.add_argument("program", help="python script to run")
    parser.add_argument("args", nargs=argparse.REMAINDER)
    opts = parser.parse_args(argv)
    if opts.with_tpu and opts.np > 1:
        # every rank would try to claim the same chip; per-rank chip
        # pinning is not implemented
        parser.error("--with-tpu runs one rank (-np 1): a TPU chip "
                     "belongs to one process at a time")

    placement: Optional[List[str]] = None
    if opts.hostfile:
        placement = plm.assign_ranks(plm.parse_hostfile(opts.hostfile),
                                     opts.np)
    elif opts.host:
        placement = plm.assign_ranks(plm.parse_host_list(opts.host),
                                     opts.np)

    multihost = placement is not None and any(
        not plm.is_local(h) for h in placement)
    if multihost:
        # remote ranks dial back over the network: listen everywhere,
        # advertise the best non-loopback address (if/reachable analog)
        from ompi_tpu.runtime.ifaces import best_local_addr

        adv = best_local_addr() or "127.0.0.1"
        server = ModexServer(opts.np, host="0.0.0.0", advertise=adv)
    else:
        server = ModexServer(opts.np)
    env_base = dict(os.environ)
    env_base["OMPI_TPU_SIZE"] = str(opts.np)
    env_base["OMPI_TPU_MODEX"] = server.address
    if multihost:
        # ranks bind/advertise their own non-loopback addresses too
        env_base["OMPI_TPU_MULTIHOST"] = "1"
    # ranks run `python script.py`, which puts the script's dir (not our
    # cwd) on sys.path — propagate the launcher's import environment so
    # `import ompi_tpu` resolves the same way it did for the launcher
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    extra = [os.getcwd(), pkg_root]
    prior = env_base.get("PYTHONPATH")
    if prior:
        extra.append(prior)
    env_base["PYTHONPATH"] = os.pathsep.join(extra)
    if not opts.with_tpu:
        # A TPU chip belongs to one process; N rank interpreters racing
        # to claim it fail or hang at startup. Process-mode ranks are
        # host-only unless explicitly opted in (the device path is mesh
        # mode's).
        env_base["JAX_PLATFORMS"] = "cpu"
    for var, value in opts.mca:
        env_base[f"OMPI_TPU_MCA_{var}"] = value

    # a SIGTERM (shell timeout, operator ^C relayed by a wrapper) must
    # run the finally block below — a default-handler death leaks every
    # rank as an orphan spinning on a dead modex (observed: stale ranks
    # from killed jobs loading the CI host for hours)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))

    procs: List[subprocess.Popen] = []
    try:
        for rank in range(opts.np):
            env = dict(env_base)
            env["OMPI_TPU_RANK"] = str(rank)
            host = placement[rank] if placement else None
            procs.append(plm.spawn_rank(host, opts.launch_agent, env,
                                        opts.program, opts.args,
                                        os.getcwd()))
        # Poll ALL children: the first abnormal exit tears down the whole
        # job immediately (reference: prterun kills the job on abnormal
        # termination) — waiting rank-by-rank would let a peer blocked on
        # the dead rank hang until the full job timeout.
        import time

        rc = 0
        deadline = time.monotonic() + opts.timeout
        remaining = set(range(opts.np))
        while remaining:
            for i in list(remaining):
                code = procs[i].poll()
                if code is not None:
                    remaining.discard(i)
                    if code != 0 and rc == 0:
                        rc = code
            if rc != 0:
                break
            if time.monotonic() > deadline:
                rc = 124
                break
            if remaining:
                time.sleep(0.05)
        if rc != 0:
            for p in procs:
                if p.poll() is None:
                    p.send_signal(signal.SIGTERM)
            grace = time.monotonic() + 2.0
            while (any(p.poll() is None for p in procs)
                   and time.monotonic() < grace):
                time.sleep(0.05)
        return rc
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        server.close()


if __name__ == "__main__":
    sys.exit(main())
