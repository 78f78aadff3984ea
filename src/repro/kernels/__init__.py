# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
"""Pallas kernel package: the one place the lowering choice is resolved.

Every kernel wrapper takes ``lowering: Optional[str] = None`` (and a
back-compat ``interpret: Optional[bool] = None``) and resolves ``None``
through :func:`resolve_lowering`, so flipping between the Pallas kernel,
the Pallas interpreter and the compiled XLA leg is a config/env decision
(``REPRO_KERNEL_LOWERING=pallas|interpret|xla``), never a code edit — the
K2 lowering-flag-hygiene contract (repro.analysis).

Legs:

* ``"pallas"``    — ``pl.pallas_call(..., interpret=False)``: the Mosaic
  kernel, TPU only (CPU XLA has no Mosaic compiler). It compiles for TPU v5e
  at real model widths (tests/test_tpu_compile.py compiles it for a
  described chip); a caller on a sharded mesh maps it over the mesh with
  ``shard_map``, since GSPMD does not partition Mosaic kernels.
* ``"interpret"`` — ``pl.pallas_call(..., interpret=True)``: the Pallas
  interpreter, runs anywhere; structural ground truth, slow.
* ``"xla"``       — the SAME blockwise math as a plain jnp program compiled
  by XLA; bit-identical to the interpreter (identical f32 expressions per
  row) and the fast compiled path on CPU, where BENCH_kernels' compiled
  rows come from.
"""
from __future__ import annotations

import os
from typing import Optional

LOWERINGS = ("pallas", "interpret", "xla")


def resolve_lowering(lowering: Optional[str] = None,
                     interpret: Optional[bool] = None) -> str:
    """Resolve the kernel lowering: ``"pallas"``/``"interpret"``/``"xla"``.

    Explicit ``lowering`` wins; else an explicit legacy ``interpret`` bool
    (True ~ interpret, False ~ pallas); else ``REPRO_KERNEL_LOWERING``;
    else the legacy ``REPRO_PALLAS_INTERPRET`` env var; else pallas on TPU
    and the compiled XLA leg everywhere else."""
    if lowering is not None:
        if lowering not in LOWERINGS:
            raise ValueError(f"lowering must be one of {LOWERINGS}, "
                             f"got {lowering!r}")
        return lowering
    if interpret is not None:
        return "interpret" if interpret else "pallas"
    env = os.environ.get("REPRO_KERNEL_LOWERING", "").strip().lower()
    if env:
        if env not in LOWERINGS:
            raise ValueError(f"REPRO_KERNEL_LOWERING must be one of "
                             f"{LOWERINGS}, got {env!r}")
        return env
    legacy = os.environ.get("REPRO_PALLAS_INTERPRET", "").strip().lower()
    if legacy in ("1", "true", "yes", "on"):
        return "interpret"
    if legacy in ("0", "false", "no", "off"):
        return "pallas"
    import jax
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def interpret_default(interpret: Optional[bool] = None) -> bool:
    """Legacy resolver kept for callers that only know the interpret bool:
    True iff :func:`resolve_lowering` lands on the interpreter."""
    if interpret is not None:
        return bool(interpret)
    return resolve_lowering() == "interpret"
