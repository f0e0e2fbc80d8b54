"""mpilint — AST linter for this project's cross-layer contracts.

The reference Open MPI holds its MCA component contracts and request
lifecycle by convention over 520k LoC; here the conventions the ROADMAP
and review rounds established (hot-path guard discipline, single-source
cvar/pvar registration, span pairing, progress-callback discipline) are
machine-checked so CI fails when a refactor breaks one. Rules:

========================  =====================================================
rule id                   contract
========================  =====================================================
hot-guard                 in hot modules (parallel/mesh.py, pml/ob1.py,
                          coll/xla.py, runtime/progress.py) every trace/
                          sanitizer/metrics instrumentation call — and every
                          ft/inject.py chaos hook, ft/diskless.py
                          replication hook, reshard/ accounting
                          hook, quant/ codec-accounting hook,
                          coll/hier note_* observability hook,
                          coll/persist replay-accounting hook, and
                          qos.py traffic-classification hook
                          (framework code allowed on
                          the wire path) — sits behind a live-Var
                          guard: ``X.enabled()`` / ``X._enable_var._value`` (or
                          a local name assigned from one) — context-manager
                          construction on the disabled path is too expensive
                          for a per-call prologue.
span-ctx                  ``trace.span(...)`` must be entered through ``with``
                          (or an assigned name used in a ``with``, or inside a
                          try/finally) — a span that never exits corrupts B/E
                          pairing in the export.
cvar-once                 each (framework, name) cvar is ``register_var``-ed at
                          exactly one source site, and nothing reads
                          ``OMPI_TPU_MCA_*`` from the environment except
                          mca/var.py (the one precedence engine).
pvar-once                 each literal pvar name is ``register_pvar``-ed at
                          exactly one source site.
raw-environ               no ``os.environ`` access outside mca/var.py and
                          ompi_tpu/tools/ — config rides the MCA var system;
                          launcher/rank-identity plumbing must carry an inline
                          suppression with justification.
request-override          Request subclasses overriding ``Wait``/``_finish``
                          must delegate (``super().Wait``/``super()._finish``
                          or ``self._finish``) so completion/raise-once
                          semantics stay centralized.
progress-blocking         no ``time.sleep``/``.wait()``/``.join()``/blocking
                          ``select()`` inside progress callbacks registered
                          with runtime/progress.py — one stalled callback
                          stalls every blocked Wait in the process.
mutable-default           no mutable default arguments ([] / {} / set()).
swallowed-mpierror        verb-layer modules (comm/, parallel/) must not
                          ``except MPIError: pass`` — a swallowed error leaves
                          requests/epochs wedged with no diagnostic.
show-help-topic           ``show_help(topic, key)`` with literal arguments must
                          reference a topic registered via ``register_topic``
                          somewhere in the package.
========================  =====================================================

Suppression: append ``# mpilint: disable=<rule>[,<rule>...]`` (or
``disable=all``) to the offending line; add the justification after the
rule list. Suppressions are per-line and per-rule by design — a blanket
file-level opt-out would rot.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Set, Tuple

from ompi_tpu.analysis import pkgmodel
from ompi_tpu.analysis.report import ERROR, WARNING, Finding

RULES: Dict[str, str] = {
    "hot-guard": "instrumentation in hot modules must sit behind a "
                 "live-Var enabled()/._value guard",
    "span-ctx": "trace.span must be entered via `with` (or try/finally)",
    "cvar-once": "cvars registered exactly once, only through mca/var",
    "pvar-once": "pvars registered exactly once",
    "raw-environ": "no os.environ reads outside mca/var and tools",
    "request-override": "Request.Wait/_finish overrides must delegate",
    "progress-blocking": "no blocking calls in progress callbacks",
    "mutable-default": "no mutable default arguments",
    "swallowed-mpierror": "verb layer must not swallow MPIError",
    "show-help-topic": "show_help topics must be registered",
    "hot-copy": "no payload duplication on the datapath: "
                "bytes(memoryview(...)), bytes(buf[...]) slicing, and "
                "+= bytes-concat on connection buffers are the copy "
                "tax the zero-copy vectored tcp path exists to kill",
    "parse-error": "every linted file must parse (a broken file would "
                   "silently escape every other rule)",
}

# module classification, by path relative to the ompi_tpu package root
HOT_MODULES = {
    "parallel/mesh.py",
    "pml/ob1.py",
    "coll/xla.py",
    "runtime/progress.py",
}
VERB_LAYER_DIRS = ("comm/", "parallel/")
# the process-mode wire datapath (hot-copy rule): modules where a frame
# or payload byte should move as a view, never a fresh bytes object —
# an intentional ownership/boundary copy carries an inline suppression
# with justification
HOT_COPY_MODULES = (
    "btl/tcp.py",
    "btl/sm.py",
    "btl/base.py",
    "btl/self_btl.py",
    "pml/ob1.py",
    "pml/base.py",
    "core/convertor.py",
    # the collective round engine rides the same discipline (PR 10):
    # round sends are borrowed views, recvs are pooled or land direct —
    # a staging materialization here re-taxes every proc-mode collective
    "coll/sched.py",
    "coll/algorithms.py",
)
ENVIRON_EXEMPT = ("mca/var.py", "tools/")
# the instrumentation implementations themselves (they define the guards)
# — for the quant plane that is ONLY quant/__init__.py (it owns the
# note_coll/note_wire hooks); codec/negotiate/coll-quant/btl-tcp are
# the plane those hooks instrument and keep full span-ctx coverage
INSTR_IMPL = ("runtime/trace.py", "runtime/sanitizer.py", "runtime/spc.py",
              "runtime/metrics.py", "ft/inject.py", "ft/diskless.py",
              "reshard/plan.py", "reshard/exec.py", "reshard/elastic.py",
              "quant/__init__.py", "coll/hier/__init__.py",
              "coll/hier/plan.py", "coll/hier/decide.py",
              "coll/hier/compose.py",
              # the round engine is instrumentation-bearing framework
              # code (PR 10): listed here so the span-ctx pairing check
              # doesn't apply to it — like the other entries, any
              # trace spans it grows are its own implementation detail
              "coll/sched.py",
              # the persistent-plan compiler owns the persist note_*
              # hooks and the replay counters (PR 11)
              "coll/persist.py",
              # the QoS module owns the classification hooks and the
              # stamped-by-class counters; the shaped tcp send path is
              # instrumentation-bearing framework code (per-class
              # deferral observations, preemption counters) riding the
              # same guard discipline
              "qos.py", "btl/tcp.py")

TRACE_ALIASES = {"trace", "_trace", "_tr"}
SAN_ALIASES = {"sanitizer", "_san", "_sanitizer"}
# ft/inject.py chaos hooks are framework code ALLOWED on the wire path —
# but only behind the same live-Var guard discipline as trace/sanitizer
INJECT_ALIASES = {"inject", "_inject"}
# runtime/metrics.py live-metrics hooks ride the same contract: entry
# stamps and latency observations in hot modules must be guarded
METRICS_ALIASES = {"metrics", "_metrics", "_mx"}
# ft/diskless.py replication hooks: an epoch save or preemption flush
# reached from hot code must sit behind the ft_ckpt_enable live Var
DISKLESS_ALIASES = {"diskless", "_diskless"}
# reshard/ accounting hooks (plan/exec pvar + spc bumps): a reshard
# note reached from hot code rides the same live-Var guard contract
RESHARD_ALIASES = {"reshard", "_reshard", "_rs"}
# quant/ codec-accounting hooks (quantized-collective byte counters and
# the btl compress counters): same contract in hot modules
QUANT_ALIASES = {"quant", "_quant", "_qc"}
# coll/hier observability hooks (plan-cache counters + per-stage
# latency observations): a note_* reached from hot code must ride the
# same one-live-Var guard
HIER_ALIASES = {"hier", "_hier"}
# coll/persist replay-accounting hooks (persistent-plan compiles,
# Start/replay-latency notes, overlap-round counts): same contract in
# hot modules — the steady-state replay path bumps list slots inline
PERSIST_ALIASES = {"persist", "_persist"}
# qos.py traffic-classification hooks: the per-send class decision and
# the segmentation/reassembly counters run on the pml send path and
# must sit behind the btl_tcp_shape_enable live Var
QOS_ALIASES = {"qos", "_qos"}
INSTR_TRACE_ATTRS = {"span", "record_span", "instant", "counter",
                     "wrap_span"}
INSTR_SAN_ATTRS = {"wrap_coll", "on_collective", "check_p2p",
                   "wait_watch", "track_request"}
INSTR_INJECT_ATTRS = {"on_op", "wire_send", "wrap_deliver"}
INSTR_METRICS_ATTRS = {"on_coll_entry", "observe", "ewma_update",
                       "gauge_set"}
INSTR_DISKLESS_ATTRS = {"save", "flush_final", "attach"}
INSTR_RESHARD_ATTRS = {"note_plan", "note_exec"}
INSTR_QUANT_ATTRS = {"note_coll", "note_wire"}
INSTR_HIER_ATTRS = {"note_stage", "note_plan_hit", "note_plan_miss",
                    "note_retune"}
INSTR_PERSIST_ATTRS = {"note_plan", "note_start", "note_overlap"}
INSTR_QOS_ATTRS = {"classify", "note_segments", "note_reassembled"}

# ---------------------------------------------------------- auto-derive
# The lists above were hand-extended by every PR that added an
# instrumentation plane — the recurring tax ISSUE 13 kills. They are now
# an override/allowlist: the EFFECTIVE sets are the union of the hand
# lists and what a package scan derives from the house conventions:
#
# - an instrumentation-impl module defines a top-level ``_enable_var``
#   assignment, a top-level ``def enabled()``, a top-level ``note_*``
#   hook, or carries an explicit ``MPILINT_INSTR_IMPL = True`` marker
#   (for plane members with no hooks of their own, e.g. the shaped tcp
#   send path);
# - its aliases are every name the package imports it under
#   (``from ompi_tpu.runtime import trace as _tr`` covers mesh.py);
# - its guarded hook-attr set is its top-level ``note_*`` functions
#   (the one naming convention every plane shares; the irregular hook
#   names — observe, classify, wire_send ... — stay hand-kept).
#
# A new plane that follows the conventions is covered by hot-guard with
# ZERO linter edits; ``python -m tools.mpilint --self-test`` proves the
# derivation still reproduces the hand-kept lists (parity).
def _pkg_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


_derived_memo: Optional[Tuple[Set[str], Dict[str, Set[str]],
                              Dict[str, Set[str]]]] = None


def derive_instr(root: Optional[str] = None):
    """Scan the package once: returns (impl module rel-paths,
    alias -> {rel modules}, rel module -> {note_* hook names})."""
    global _derived_memo
    if root is None and _derived_memo is not None:
        return _derived_memo
    from ompi_tpu.analysis import pkgmodel

    pkg = pkgmodel.load_package([root or _pkg_root()])
    impl: Set[str] = set()
    attr_map: Dict[str, Set[str]] = {}
    for mod in pkg.modules.values():
        if mod.tree is None or mod.relp.startswith("analysis/"):
            continue
        notes: Set[str] = set()
        is_impl = "MPILINT_INSTR_IMPL" in mod.globals
        for stmt in mod.tree.body:
            if isinstance(stmt, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == "_enable_var"
                    for t in stmt.targets):
                is_impl = True
            elif isinstance(stmt, ast.FunctionDef):
                if stmt.name == "enabled":
                    is_impl = True
                elif stmt.name.startswith("note_"):
                    is_impl = True
                    notes.add(stmt.name)
        if is_impl:
            impl.add(mod.relp)
            attr_map[mod.relp] = notes
    alias_map: Dict[str, Set[str]] = {}
    dotted_impl = {m.dotted: m.relp for m in pkg.modules.values()
                   if m.relp in impl}
    for mod in pkg.modules.values():
        if mod.tree is None:
            continue
        for alias, dotted in mod.mod_aliases.items():
            relp = dotted_impl.get(dotted)
            if relp is not None:
                alias_map.setdefault(alias, set()).add(relp)
    for dotted, relp in dotted_impl.items():
        alias_map.setdefault(dotted.rsplit(".", 1)[-1],
                             set()).add(relp)
    if root is None:
        _derived_memo = (impl, alias_map, attr_map)
        _dotted_impl_memo.update(dotted_impl)
    return impl, alias_map, attr_map


_dotted_impl_memo: Dict[str, str] = {}


def _file_instr_aliases(tree: ast.Module) -> Dict[str, str]:
    """The linted file's OWN import aliases that resolve to derived
    instrumentation-impl modules (alias -> rel path). A file that does
    ``from ompi_tpu.ft import diskless as _d`` gets hook coverage for
    ``_d.note_*`` no matter what the rest of the package calls it."""
    derive_instr()  # populate _dotted_impl_memo
    out: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                relp = _dotted_impl_memo.get(a.name)
                if relp is not None:
                    out[a.asname or a.name.split(".")[0]] = relp
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                relp = _dotted_impl_memo.get(f"{node.module}.{a.name}")
                if relp is not None:
                    out[a.asname or a.name] = relp
    return out


def effective_instr_impl() -> Set[str]:
    impl, _aliases, _attrs = derive_instr()
    return impl | set(INSTR_IMPL)


def _derived_hook(alias: str, attr: str,
                  local: Optional[Dict[str, str]] = None) -> Optional[str]:
    """Plane label when alias.attr is a derived note_* hook — resolved
    through the linted file's own imports first, then the package-wide
    alias scan."""
    if not attr.startswith("note_"):
        return None
    _impl, alias_map, attr_map = derive_instr()
    relps = set(alias_map.get(alias, ()))
    if local and alias in local:
        relps.add(local[alias])
    for relp in relps:
        if attr in attr_map.get(relp, ()):
            return os.path.basename(relp)[:-3]
    return None


def derive_parity():
    """Parity of the derivation vs the hand-kept lists: returns
    (hand impl modules the scan FAILED to derive,
     derived-only impl modules the hand list doesn't carry,
     hand aliases the package never imports — dead allowlist entries).
    The first set must stay empty (the --self-test gate): a refactor
    that breaks a convention would silently shrink hot-guard coverage
    back to the hand lists."""
    impl, alias_map, _attrs = derive_instr()
    missing_impl = set(INSTR_IMPL) - impl
    extra_impl = impl - set(INSTR_IMPL)
    hand_aliases: Set[str] = set()
    for s in (TRACE_ALIASES, SAN_ALIASES, INJECT_ALIASES,
              METRICS_ALIASES, DISKLESS_ALIASES, RESHARD_ALIASES,
              QUANT_ALIASES, HIER_ALIASES, PERSIST_ALIASES,
              QOS_ALIASES):
        hand_aliases |= s
    dead_aliases = hand_aliases - set(alias_map)
    return missing_impl, extra_impl, dead_aliases


def rel_path(path: str) -> str:
    """Path relative to the ompi_tpu package root (forward slashes), or
    the basename for files outside the package (tools/, snippets)."""
    parts = os.path.normpath(path).split(os.sep)
    if "ompi_tpu" in parts:
        i = len(parts) - 1 - parts[::-1].index("ompi_tpu")
        return "/".join(parts[i + 1:])
    return parts[-1]


def _suppressions(src: str) -> Dict[int, Set[str]]:
    # the shared pkgmodel grammar: the old local regex was greedy, so a
    # two-rule list with an ASCII `--` justification separator
    # (`disable=a,b -- why`) swallowed the separator and the reason
    # into the rule names and only the FIRST rule actually applied
    out: Dict[int, Set[str]] = {}
    for i, line in enumerate(src.splitlines(), 1):
        got = pkgmodel.parse_suppression(line, "mpilint")
        if got is not None:
            out[i] = got[0]
    return out


class FileScan:
    """Per-file findings plus the cross-file facts (registrations)."""

    def __init__(self, path: str, relp: str, suppress: Dict[int, Set[str]]):
        self.path = path
        self.relp = relp
        self.suppress = suppress
        self.findings: List[Finding] = []
        self.cvars: List[Tuple[str, int]] = []    # (framework_name, line)
        self.pvars: List[Tuple[str, int]] = []
        self.topics: Set[Tuple[str, str]] = set()
        self.helps: List[Tuple[str, str, int]] = []  # (topic, key, line)

    def add(self, rule: str, line: int, message: str,
            severity: str = ERROR, hint: str = "") -> None:
        sup = self.suppress.get(line, ())
        if rule in sup or "all" in sup:
            return
        self.findings.append(Finding(rule, self.path, line, message,
                                     severity, hint))


# --------------------------------------------------------------- helpers
def _call_name(node: ast.Call) -> str:
    f = node.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return ""


def _str_arg(node: ast.Call, i: int) -> Optional[str]:
    if i < len(node.args):
        a = node.args[i]
        if isinstance(a, ast.Constant) and isinstance(a.value, str):
            return a.value
    return None


def _is_guard_expr(node: ast.AST, guard_names: Set[str]) -> bool:
    """Does this expression read a live-Var gate? Accepts ``X.enabled()``,
    ``X._enable_var._value``, and names previously assigned from one."""
    for n in ast.walk(node):
        if isinstance(n, ast.Call):
            f = n.func
            if isinstance(f, ast.Attribute) and f.attr in ("enabled",
                                                           "_enabled"):
                return True
            if isinstance(f, ast.Name) and f.id in ("enabled", "_enabled"):
                return True
        elif isinstance(n, ast.Attribute) and n.attr == "_value":
            v = n.value
            if isinstance(v, ast.Name) and v.id.endswith("_enable_var"):
                return True
            if isinstance(v, ast.Attribute) and \
                    v.attr.endswith("_enable_var"):
                return True
        elif isinstance(n, ast.Name) and n.id in guard_names:
            return True
    return False


def _instr_call(node: ast.AST,
                local: Optional[Dict[str, str]] = None) -> Optional[str]:
    """'trace' / 'sanitizer' / 'inject' when node is an
    instrumentation (or fault-injection hook) call."""
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
        v = node.func.value
        if isinstance(v, ast.Name):
            if v.id in TRACE_ALIASES and \
                    node.func.attr in INSTR_TRACE_ATTRS:
                return "trace"
            if v.id in SAN_ALIASES and node.func.attr in INSTR_SAN_ATTRS:
                return "sanitizer"
            if v.id in INJECT_ALIASES and \
                    node.func.attr in INSTR_INJECT_ATTRS:
                return "inject"
            if v.id in METRICS_ALIASES and \
                    node.func.attr in INSTR_METRICS_ATTRS:
                return "metrics"
            if v.id in DISKLESS_ALIASES and \
                    node.func.attr in INSTR_DISKLESS_ATTRS:
                return "diskless"
            if v.id in RESHARD_ALIASES and \
                    node.func.attr in INSTR_RESHARD_ATTRS:
                return "reshard"
            if v.id in QUANT_ALIASES and \
                    node.func.attr in INSTR_QUANT_ATTRS:
                return "quant"
            if v.id in HIER_ALIASES and \
                    node.func.attr in INSTR_HIER_ATTRS:
                return "hier"
            if v.id in PERSIST_ALIASES and \
                    node.func.attr in INSTR_PERSIST_ATTRS:
                return "persist"
            if v.id in QOS_ALIASES and \
                    node.func.attr in INSTR_QOS_ATTRS:
                return "qos"
            # auto-derived planes: any note_* hook of a scanned impl
            # module, through any alias the package imports it under
            return _derived_hook(v.id, node.func.attr, local)
    return None


def _span_call(node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "span"
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id in TRACE_ALIASES)


# ------------------------------------------------------------- hot-guard
def _check_hot_guard(tree: ast.Module, scan: FileScan,
                     local: Optional[Dict[str, str]] = None) -> None:
    def leaf_scan(stmt: ast.stmt, guarded: bool) -> None:
        if guarded:
            return
        for n in ast.walk(stmt):
            kind = _instr_call(n, local)
            if kind is not None:
                scan.add(
                    "hot-guard", n.lineno,
                    f"{kind} instrumentation call "
                    f"`{ast.unparse(n.func)}(...)` is not dominated by a "
                    "live-Var guard in a hot module",
                    hint="wrap the call site in `if <mod>.enabled():` "
                         "(one attribute load on the disabled path)")

    def visit(body: List[ast.stmt], guarded: bool,
              guard_names: Set[str]) -> None:
        for node in body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                visit(node.body, False, set())
            elif isinstance(node, ast.ClassDef):
                visit(node.body, False, set())
            elif isinstance(node, ast.If):
                g = guarded or _is_guard_expr(node.test, guard_names)
                visit(node.body, g, guard_names)
                visit(node.orelse, guarded, guard_names)
            elif isinstance(node, (ast.For, ast.While, ast.With)):
                if isinstance(node, ast.With):
                    for item in node.items:
                        leaf_scan(item.context_expr, guarded)  # type: ignore[arg-type]
                visit(node.body, guarded, guard_names)
                visit(getattr(node, "orelse", []), guarded, guard_names)
            elif isinstance(node, ast.Try):
                visit(node.body, guarded, guard_names)
                for h in node.handlers:
                    visit(h.body, guarded, guard_names)
                visit(node.orelse, guarded, guard_names)
                visit(node.finalbody, guarded, guard_names)
            else:
                if isinstance(node, ast.Assign) and \
                        _is_guard_expr(node.value, guard_names):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            guard_names.add(t.id)
                    continue  # the guard read itself is not a violation
                leaf_scan(node, guarded)

    visit(tree.body, False, set())


# --------------------------------------------------------------- span-ctx
def _check_span_ctx(tree: ast.Module, scan: FileScan) -> None:
    with_call_ids: Set[int] = set()
    with_names: Set[str] = set()
    finally_ranges: List[Tuple[int, int]] = []
    assigned_ok: Set[int] = set()

    for node in ast.walk(tree):
        if isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                ce = item.context_expr
                if isinstance(ce, ast.Call):
                    with_call_ids.add(id(ce))
                elif isinstance(ce, ast.Name):
                    with_names.add(ce.id)
        elif isinstance(node, ast.Try) and node.finalbody:
            end = max((getattr(n, "end_lineno", n.lineno) or n.lineno)
                      for n in node.body)
            finally_ranges.append((node.body[0].lineno, end))

    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and _span_call(node.value):
            if any(isinstance(t, ast.Name) and t.id in with_names
                   for t in node.targets):
                assigned_ok.add(id(node.value))

    for node in ast.walk(tree):
        if not _span_call(node):
            continue
        if id(node) in with_call_ids or id(node) in assigned_ok:
            continue
        if any(a <= node.lineno <= b for a, b in finally_ranges):
            continue
        scan.add("span-ctx", node.lineno,
                 "trace span created outside a `with` statement — B/E "
                 "pairing is not guaranteed to close",
                 hint="use `with trace.span(...):` or pair __enter__/"
                      "__exit__ under try/finally")


# ---------------------------------------------------- registries + environ
def _check_registrations(tree: ast.Module, scan: FileScan) -> None:
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node)
        if name == "register_var":
            fw, vn = _str_arg(node, 0), _str_arg(node, 1)
            if fw is not None and vn is not None:
                scan.cvars.append((f"{fw}_{vn}", node.lineno))
        elif name == "register_pvar":
            fw, vn = _str_arg(node, 0), _str_arg(node, 1)
            if fw is not None and vn is not None:
                scan.pvars.append((f"{fw}_{vn}", node.lineno))
        elif name == "register_topic":
            t, k = _str_arg(node, 0), _str_arg(node, 1)
            if t is not None and k is not None:
                scan.topics.add((t, k))
        elif name == "show_help":
            t, k = _str_arg(node, 0), _str_arg(node, 1)
            if t is not None and k is not None:
                scan.helps.append((t, k, node.lineno))


def _check_environ(tree: ast.Module, scan: FileScan) -> None:
    exempt = any(scan.relp == e or scan.relp.startswith(e)
                 for e in ENVIRON_EXEMPT)
    seen: Set[int] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr == "environ" \
                and isinstance(node.value, ast.Name) \
                and node.value.id == "os":
            if not exempt and node.lineno not in seen:
                seen.add(node.lineno)
                scan.add(
                    "raw-environ", node.lineno,
                    "os.environ accessed outside mca/var and tools — "
                    "config must ride the MCA var precedence engine",
                    hint="register_var()/get_var(), or suppress with "
                         "justification for launcher/identity plumbing")
    # OMPI_TPU_MCA_* env literals anywhere else bypass source precedence
    # (mca/var is the precedence engine, tools/ is the launcher that
    # WRITES the env for child ranks, analysis/ embeds bad-code snippets)
    if scan.relp != "mca/var.py" and \
            not scan.relp.startswith(("tools/", "analysis/")):
        for node in ast.walk(tree):
            if isinstance(node, ast.Constant) \
                    and isinstance(node.value, str) \
                    and node.value.startswith("OMPI_TPU_MCA_"):
                scan.add("cvar-once", node.lineno,
                         f"literal {node.value!r} environment access "
                         "outside mca/var bypasses cvar source precedence",
                         hint="read the registered Var instead")


# -------------------------------------------------------- request-override
def _check_request_override(tree: ast.Module, scan: FileScan) -> None:
    for node in ast.walk(tree):
        if not isinstance(node, ast.ClassDef):
            continue
        base_names = []
        for b in node.bases:
            if isinstance(b, ast.Name):
                base_names.append(b.id)
            elif isinstance(b, ast.Attribute):
                base_names.append(b.attr)
        if not any("Request" in b for b in base_names):
            continue
        for meth in node.body:
            if not isinstance(meth, ast.FunctionDef) or \
                    meth.name not in ("Wait", "_finish"):
                continue
            delegates = False
            for n in ast.walk(meth):
                if not (isinstance(n, ast.Call)
                        and isinstance(n.func, ast.Attribute)):
                    continue
                if n.func.attr not in ("Wait", "_finish"):
                    continue
                v = n.func.value
                if isinstance(v, ast.Call) and \
                        isinstance(v.func, ast.Name) and \
                        v.func.id == "super":
                    delegates = True
                elif isinstance(v, ast.Name) and v.id in ("Request",
                                                          "self"):
                    # Request.Wait(...) or self._finish(...) from Wait
                    delegates = True
            if not delegates:
                scan.add(
                    "request-override", meth.lineno,
                    f"{node.name}.{meth.name} overrides Request."
                    f"{meth.name} without delegating — completion/"
                    "raise-once semantics live in the base class",
                    hint=f"call super().{meth.name}(...) (or self._finish "
                         "from Wait) on every exit path")


# ------------------------------------------------------- progress-blocking
_BLOCKING_ATTRS = ("sleep", "join", "wait")


def _check_progress_blocking(tree: ast.Module, scan: FileScan) -> None:
    registered: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and \
                _call_name(node) == "register_progress":
            for a in node.args[:1]:
                if isinstance(a, ast.Name):
                    registered.add(a.id)
                elif isinstance(a, ast.Attribute):
                    registered.add(a.attr)

    def check_fn(fn: ast.FunctionDef, where: str) -> None:
        for n in ast.walk(fn):
            if not isinstance(n, ast.Call):
                continue
            f = n.func
            if isinstance(f, ast.Attribute) and f.attr in _BLOCKING_ATTRS:
                # select/poll with a 0 timeout is a poll, not a block
                scan.add(
                    "progress-blocking", n.lineno,
                    f"`{ast.unparse(f)}(...)` inside progress callback "
                    f"{where} can stall every blocked Wait in the process",
                    hint="poll nonblockingly and return 0; leave yielding "
                         "to the shared IdleBackoff discipline")
            elif isinstance(f, ast.Attribute) and f.attr == "select":
                timeouts = list(n.args[:1]) + [
                    kw.value for kw in n.keywords
                    if kw.arg == "timeout"]
                if not any(isinstance(t, ast.Constant) and t.value == 0
                           for t in timeouts):
                    scan.add(
                        "progress-blocking", n.lineno,
                        f"blocking select() inside progress callback "
                        f"{where}",
                        hint="use select(0) so the callback never blocks")

    # locally-registered functions, plus btl progress methods (wireup
    # registers `mod.progress` for every selected transport)
    is_btl = scan.relp.startswith("btl/")
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef):
            if node.name in registered:
                check_fn(node, f"{node.name}()")
            elif is_btl and node.name == "progress":
                check_fn(node, f"{scan.relp}:{node.name}()")


# --------------------------------------------------------- mutable-default
def _check_mutable_default(tree: ast.Module, scan: FileScan) -> None:
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        defaults = list(node.args.defaults) + \
            [d for d in node.args.kw_defaults if d is not None]
        for d in defaults:
            bad = isinstance(d, (ast.List, ast.Dict, ast.Set)) or (
                isinstance(d, ast.Call) and isinstance(d.func, ast.Name)
                and d.func.id in ("list", "dict", "set", "bytearray"))
            if bad:
                scan.add(
                    "mutable-default", d.lineno,
                    f"mutable default argument in {node.name}() is shared "
                    "across calls",
                    hint="default to None and materialize inside the body")


# ---------------------------------------------------------------- hot-copy
# conn-buffer attribute names for the += concat check: the old wbuf/rbuf
# bytes-concat queues were O(n^2) under backlog, and any new *buf
# accumulator on a connection object is the same trap
_BUF_ATTR_SUFFIXES = ("buf",)


def _check_hot_copy(tree: ast.Module, scan: FileScan) -> None:
    if scan.relp not in HOT_COPY_MODULES:
        return
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Name) and \
                node.func.id in ("bytes", "bytearray") and node.args:
            arg = node.args[0]
            # bytes(memoryview(...)) / bytes(mv.cast(...)): a full
            # payload materialization of something that was already a
            # view
            if any(isinstance(n, ast.Call)
                   and isinstance(n.func, ast.Name)
                   and n.func.id == "memoryview"
                   for n in ast.walk(arg)):
                scan.add(
                    "hot-copy", node.lineno,
                    "bytes(memoryview(...)) materializes a payload that "
                    "was already a zero-copy view",
                    hint="pass the view through (sendmsg/recv_into take "
                         "buffers); if ownership is genuinely required "
                         "at this boundary, suppress with justification")
            # bytes(buf[a:b]) parse-copy: slice the view instead
            elif isinstance(arg, ast.Subscript):
                scan.add(
                    "hot-copy", node.lineno,
                    "bytes(<buffer>[...]) duplicates a frame slice — "
                    "the datapath hands out views, copies happen only "
                    "at the delivery boundary",
                    hint="use a memoryview slice; a deliberate boundary "
                         "copy takes an inline suppression")
        elif isinstance(node, ast.Call) and (
                (isinstance(node.func, ast.Attribute)
                 and node.func.attr in ("ascontiguousarray",
                                        "concatenate"))
                or (isinstance(node.func, ast.Name)
                    and node.func.id in ("ascontiguousarray",
                                         "concatenate"))):
            # np.ascontiguousarray / np.concatenate: the coll-round
            # staging tax (a defensive ascontiguousarray on an
            # already-contiguous view is dead weight; a real one is a
            # payload materialization that must be counted)
            scan.add(
                "hot-copy", node.lineno,
                f"np.{getattr(node.func, 'attr', None) or node.func.id}"
                "(...) stages a payload on the datapath — round sends "
                "borrow contiguous views, recvs land in pooled blocks "
                "or their final slot",
                hint="pass the view through (1-D slices of contiguous "
                     "buffers are already contiguous); a genuine "
                     "non-contiguous fallback or legacy A/B copy takes "
                     "an inline suppression and a note_copied() charge")
        elif isinstance(node, ast.AugAssign) and \
                isinstance(node.op, ast.Add) and \
                isinstance(node.target, ast.Attribute) and \
                node.target.attr.endswith(_BUF_ATTR_SUFFIXES):
            scan.add(
                "hot-copy", node.lineno,
                f"`{ast.unparse(node.target)} +=` rebuilds a connection "
                "buffer per frame (O(n^2) under backlog — the wbuf/rbuf "
                "concat tax)",
                hint="queue views in a deque and drain with vectored "
                     "I/O (btl/tcp.py's wq/sendmsg pattern)")


# ------------------------------------------------------ swallowed-mpierror
def _check_swallowed_mpierror(tree: ast.Module, scan: FileScan) -> None:
    if not any(scan.relp.startswith(d) for d in VERB_LAYER_DIRS):
        return
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler) or node.type is None:
            continue
        names = [n.id if isinstance(n, ast.Name) else
                 n.attr if isinstance(n, ast.Attribute) else ""
                 for n in ast.walk(node.type)]
        if "MPIError" not in names:
            continue
        if len(node.body) == 1 and isinstance(node.body[0], ast.Pass):
            scan.add(
                "swallowed-mpierror", node.lineno,
                "MPIError swallowed with a bare pass in the verb layer — "
                "the caller's request/epoch is left wedged silently",
                hint="complete the request with the error code, log, or "
                     "re-raise")


# ----------------------------------------------------------- file scanning
def scan_source(src: str, path: str) -> FileScan:
    relp = rel_path(path)
    scan = FileScan(path, relp, _suppressions(src))
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        scan.add("parse-error", e.lineno or 0,
                 f"unparseable file: {e.msg}")
        return scan
    _check_registrations(tree, scan)
    _check_environ(tree, scan)
    _check_request_override(tree, scan)
    _check_progress_blocking(tree, scan)
    _check_mutable_default(tree, scan)
    _check_swallowed_mpierror(tree, scan)
    _check_hot_copy(tree, scan)
    if relp not in effective_instr_impl():
        _check_span_ctx(tree, scan)
    if relp in HOT_MODULES:
        _check_hot_guard(tree, scan, _file_instr_aliases(tree))
    return scan


def _cross_file(scans: List[FileScan]) -> List[Finding]:
    findings: List[Finding] = []

    def dup_check(attr: str, rule: str, what: str) -> None:
        sites: Dict[str, List[Tuple[FileScan, int]]] = {}
        for s in scans:
            for key, line in getattr(s, attr):
                sites.setdefault(key, []).append((s, line))
        for key, where in sorted(sites.items()):
            if len(where) <= 1:
                continue
            first = where[0]
            for s, line in where[1:]:
                sup = s.suppress.get(line, ())
                if rule in sup or "all" in sup:
                    continue
                findings.append(Finding(
                    rule, s.path, line,
                    f"{what} '{key}' already registered at "
                    f"{first[0].relp}:{first[1]} — names must be "
                    "registered exactly once",
                    hint="share the Var/Pvar handle instead of "
                         "re-registering"))

    dup_check("cvars", "cvar-once", "cvar")
    dup_check("pvars", "pvar-once", "pvar")

    topics = set()
    for s in scans:
        topics |= s.topics
    for s in scans:
        for t, k, line in s.helps:
            if (t, k) in topics:
                continue
            sup = s.suppress.get(line, ())
            if "show-help-topic" in sup or "all" in sup:
                continue
            findings.append(Finding(
                "show-help-topic", s.path, line,
                f"show_help('{t}', '{k}') has no matching register_topic "
                "in the package — it would render a [no help ...] stub",
                hint="register_topic the message next to the subsystem "
                     "that raises it"))
    return findings


def lint_paths(paths: List[str]) -> List[Finding]:
    """Lint files and/or directory trees; cross-file rules see the whole
    set at once."""
    files: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, _dirs, names in os.walk(p):
                for n in sorted(names):
                    if n.endswith(".py"):
                        files.append(os.path.join(root, n))
        else:
            files.append(p)
    scans = []
    for f in files:
        with open(f, encoding="utf-8") as fh:
            scans.append(scan_source(fh.read(), f))
    findings = [x for s in scans for x in s.findings]
    findings += _cross_file(scans)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings


def lint_source(src: str, path: str = "<string>") -> List[Finding]:
    """Single-source entry (self-test, unit tests): per-file rules plus
    the cross-file rules evaluated over just this source."""
    scan = scan_source(src, path)
    return scan.findings + _cross_file([scan])


# ------------------------------------------------------------- self-test
# One intentionally-bad snippet per rule; the fake path controls the
# path-scoped rules (hot modules, verb layer). `python -m tools.mpilint
# --self-test` lints each and verifies its rule fires.
SELF_TEST_SNIPPETS: Dict[str, Tuple[str, str]] = {
    "hot-guard": ("ompi_tpu/pml/ob1.py", """
from ompi_tpu import qos as _qos
from ompi_tpu import quant as _quant
from ompi_tpu.coll import hier as _hier
from ompi_tpu.coll import persist as _persist
from ompi_tpu.ft import diskless as _diskless
from ompi_tpu.ft import inject as _inject
from ompi_tpu.reshard import exec as _reshard
from ompi_tpu.runtime import metrics as _metrics
from ompi_tpu.runtime import trace as _trace

def isend(self, dst):
    _inject.on_op(self.my_rank, 0)
    _metrics.observe("pml_send_latency_us", 1.0, peer=dst)
    _diskless.flush_final(0.1)
    _reshard.note_exec(1, 2)
    _quant.note_wire(4096, 512)
    _hier.note_stage("allreduce", "cross", 1.0)
    _persist.note_start(1.0)
    _qos.classify(0, 0)
    with _trace.span("pml.send", cat="pml"):
        return self._isend(dst)
"""),
    "span-ctx": ("ompi_tpu/comm/communicator.py", """
from ompi_tpu.runtime import trace

def barrier(comm):
    s = trace.span("comm.barrier", cat="comm")
    s.__enter__()
    comm._coll("barrier")(comm)
"""),
    "cvar-once": ("ompi_tpu/coll/tuned.py", """
from ompi_tpu.mca.var import register_var

register_var("coll_tuned", "segsize", 1 << 16, help="segment size")
register_var("coll_tuned", "segsize", 1 << 20, help="segment size again")
"""),
    "pvar-once": ("ompi_tpu/pml/monitoring.py", """
from ompi_tpu.mca.var import register_pvar

register_pvar("pml", "queue_depth", lambda: 0)
register_pvar("pml", "queue_depth", lambda: 1)
"""),
    "raw-environ": ("ompi_tpu/coll/basic.py", """
import os

def segsize():
    return int(os.environ.get("OMPI_TPU_MCA_coll_segsize", "65536"))
"""),
    "request-override": ("ompi_tpu/coll/sched.py", """
from ompi_tpu.core.request import Request

class EagerRequest(Request):
    def _finish(self, status):
        if self._error:
            raise RuntimeError(self._error)
"""),
    "progress-blocking": ("ompi_tpu/btl/tcp.py", """
import time
from ompi_tpu.runtime.progress import register_progress

def progress_cb():
    time.sleep(0.01)
    return 0

register_progress(progress_cb)
"""),
    "mutable-default": ("ompi_tpu/comm/communicator.py", """
def Split(self, color, members=[]):
    members.append(color)
    return members
"""),
    "swallowed-mpierror": ("ompi_tpu/comm/communicator.py", """
from ompi_tpu.core.errors import MPIError

def Isend(self, buf, dest):
    try:
        return self.pml.isend(buf, dest)
    except MPIError:
        pass
"""),
    "show-help-topic": ("ompi_tpu/ft/revoke.py", """
from ompi_tpu.utils.show_help import show_help

def revoke(comm):
    show_help("ft", "no-such-topic", name=comm.name)
"""),
    "hot-copy": ("ompi_tpu/coll/sched.py", """
import numpy as np

def _drain(self, conn, data):
    conn.rbuf += data
    hdr = bytes(conn.rbuf[0:49])
    payload = bytes(memoryview(data))
    staged = np.ascontiguousarray(payload)
    train = np.concatenate([staged, staged])
    return hdr, train
"""),
    "parse-error": ("ompi_tpu/coll/basic.py", """
def broken(:
    return
"""),
}


def self_test() -> Tuple[List[Finding], List[str]]:
    """Lint every embedded bad snippet. Returns (all findings, rule ids
    that FAILED to fire on their snippet)."""
    findings: List[Finding] = []
    missed: List[str] = []
    for rule, (fake_path, src) in SELF_TEST_SNIPPETS.items():
        got = lint_source(src, fake_path)
        findings.extend(got)
        if not any(f.rule == rule for f in got):
            missed.append(rule)
    return findings, missed
