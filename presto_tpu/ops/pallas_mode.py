"""The ONE place that decides how a Pallas kernel executes, and that
records which program a kernel family's step was built from.

On a TPU backend kernels are compiled by Mosaic; a refusal is the
compiler's error and reaches the caller (no probe catches it). On any
other backend — the CPU the tests run on — the same kernel bodies run
in Pallas interpret mode. ``chip_smoke.py`` asserts
``kernel_mode() == "mosaic"`` before it runs a query, so a run that
lost the chip cannot pass through the interpreter.
"""

from __future__ import annotations

import jax

#: ``dist_join`` / ``dist_agg``: the mesh's repartition join and its
#: partial -> shuffle -> final aggregation, which have no Pallas kernel
FAMILIES = ("q1", "leaf_agg", "groupby", "join", "strings", "dist_join",
            "dist_agg")


def kernel_mode() -> str:
    """"mosaic" (compiled for the attached TPU) or "interpret"."""
    return "mosaic" if jax.default_backend() == "tpu" else "interpret"


def interpret(override: bool | None = None) -> bool:
    """The ``interpret=`` argument of every ``pl.pallas_call`` in
    ``ops/pallas_*``; ``override`` is the explicit choice of tests and
    compile rehearsals (``interpret=False`` lowers for a described
    chip from a CPU process)."""
    return kernel_mode() == "interpret" if override is None else override


def count_program(family: str, pallas: bool) -> None:
    """Bump ``kernel.<family>.{mosaic,interpret,xla}`` where a family
    picks its program. Called at TRACE time of the step that embeds
    the choice, so a cold query shows what its executable was built
    from and a warm (untraced) run adds nothing — the executable, and
    so the answer, is unchanged."""
    from presto_tpu.runtime.metrics import REGISTRY

    assert family in FAMILIES, family
    kind = kernel_mode() if pallas else "xla"
    REGISTRY.counter(f"kernel.{family}.{kind}").add()
