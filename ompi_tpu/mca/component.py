"""Framework / component registry with priority selection.

Reference contracts:
- framework lifecycle: opal/mca/base/mca_base_framework.c:161 (open)
- component discovery + repository: mca_base_component_repository.c:365
- priority selection: mca_base_components_select.c and, for the per-function
  winner-takes-slot model used by collectives, coll_base_comm_select.c:216.

A ``Framework`` owns named ``Component`` classes. Selection asks each
component to ``query(**ctx)`` and returns modules ordered by priority; a
component may decline by returning None. The ``<framework>`` MCA string var
(e.g. ``OMPI_TPU_MCA_coll_coll=xla,basic``) restricts/orders candidates the
same way the reference's ``--mca coll ...`` include/exclude lists do.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Tuple

from ompi_tpu.mca.var import register_var, get_var
from ompi_tpu.utils.output import get_logger


class ComponentFatal(Exception):
    """Raised by a component's query when its resource is present but
    broken (a device that fails to come up): selection stops instead of
    falling back to a lower-priority component."""


class Component:
    """Base class for all MCA components.

    Subclasses set ``NAME`` and ``PRIORITY`` and implement ``query`` to
    return a *module* (any object implementing the framework's contract) or
    None to decline (reference: each component's component_query function).
    """

    NAME: str = "base"
    PRIORITY: int = 0

    def query(self, **ctx: Any) -> Optional[Any]:
        raise NotImplementedError

    # Lifecycle hooks (reference: mca_base_component open/close fns)
    def open(self) -> None:
        pass

    def close(self) -> None:
        pass


class Framework:
    def __init__(self, name: str, description: str = ""):
        self.name = name
        self.description = description
        self.components: Dict[str, Component] = {}
        self._opened = False
        self.log = get_logger(f"mca.{name}")
        # The selection-list var, like the reference's `--mca <fw> a,b` /
        # `--mca <fw> ^c` include/exclude syntax.
        register_var(
            name,
            name,
            "",
            str,
            help=f"Comma list of {name} components to allow "
            f"(empty=all; prefix ^ to exclude)",
            level=2,
        )

    def register(self, component: Component) -> Component:
        self.components[component.NAME] = component
        return component

    def open(self) -> None:
        if self._opened:
            return
        for comp in self.components.values():
            comp.open()
        self._opened = True

    def close(self) -> None:
        if not self._opened:
            return
        for comp in self.components.values():
            comp.close()
        self._opened = False

    def _candidates(self) -> List[Component]:
        spec = get_var(self.name, self.name).strip()
        comps = list(self.components.values())
        if spec:
            if spec.startswith("^"):
                banned = set(spec[1:].split(","))
                comps = [c for c in comps if c.NAME not in banned]
            else:
                wanted = spec.split(",")
                by_name = {c.NAME: c for c in comps}
                comps = [by_name[n] for n in wanted if n in by_name]
        return comps

    def select_all(self, **ctx: Any) -> List[Tuple[int, str, Any]]:
        """Query every candidate; return [(priority, name, module)] sorted
        descending by priority (reference: coll_base_comm_select.c:358)."""
        self.open()
        out: List[Tuple[int, str, Any]] = []
        for comp in self._candidates():
            try:
                module = comp.query(**ctx)
            except ComponentFatal:
                raise
            except Exception as e:  # a broken component must not kill init
                self.log.warning("component %s query failed: %s", comp.NAME, e)
                continue
            if module is not None:
                out.append((comp.PRIORITY, comp.NAME, module))
        out.sort(key=lambda t: (-t[0], t[1]))
        if out:
            from ompi_tpu.mpit import emit  # MPI_T event (mpit.py)

            emit("mca", "component_selected", framework=self.name,
                 component=out[0][1], priority=out[0][0])
        return out

    def select_one(self, **ctx: Any) -> Tuple[str, Any]:
        """Winner-takes-all selection (reference: pml_base_select.c:70 —
        exactly one PML per job)."""
        mods = self.select_all(**ctx)
        if not mods:
            raise RuntimeError(
                f"no usable component in framework '{self.name}' "
                f"(registered: {sorted(self.components)})"
            )
        prio, name, module = mods[0]
        self.log.debug("selected %s/%s (priority %d)", self.name, name, prio)
        return name, module


_lock = threading.Lock()
_frameworks: Dict[str, Framework] = {}


def framework(name: str, description: str = "") -> Framework:
    with _lock:
        fw = _frameworks.get(name)
        if fw is None:
            fw = Framework(name, description)
            _frameworks[name] = fw
        return fw


def register_component(framework_name: str, component: Component) -> Component:
    return framework(framework_name).register(component)


def all_frameworks() -> Dict[str, Framework]:
    return dict(_frameworks)
