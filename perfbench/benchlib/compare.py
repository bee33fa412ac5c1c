"""The arithmetic that decides `correct`: gaps between what the program
produced and what the plain reference produced from the same seed.

- `rel_gap(a, b)`: |a - b| / |b|, for losses.
- `norm_gaps(prog, ref, skip)`: for per-leaf norms (of a gradient, or of a
  change of the parameters), the gap between the program's norm and the
  reference's, over the larger of the reference's norm of that leaf and
  the median leaf's; the worst leaf counts (`worst`), or where the worst
  is noise, the median leaf (`median`).
- `quiet_leaves(ref_grad_norms)`: leaves whose reference gradient is under
  a thousandth of the median leaf's. Under Adam such leaves move by
  round-off alone, so their change is not compared.
- `quant_fp8(x)`, `fp8_round(x)`, `bf16_round(x)`: the roundings the
  control computes and stores in, one precision below what a configuration
  states.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Sequence

import numpy as np


def rel_gap(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


def quiet_leaves(ref_grad_norms: Dict[str, float]) -> List[str]:
    med = float(np.median(list(ref_grad_norms.values())))
    return sorted(k for k, v in ref_grad_norms.items() if v < 1e-3 * med)


def norm_gaps(prog: Dict[str, float], ref: Dict[str, float],
              skip: Iterable[str] = ()) -> Dict[str, float]:
    skip = set(skip)
    keys = [k for k in ref if k not in skip]
    floor = float(np.median([ref[k] for k in ref]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], floor, 1e-30)
            for k in keys}


def worst(gaps: Dict[str, float]) -> float:
    return max(gaps.values()) if gaps else 0.0


def median(gaps: Dict[str, float]) -> float:
    return float(np.median(list(gaps.values()))) if gaps else 0.0


def _fp8(x):
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def quant_fp8(x):
    """Round `x` to float8 e4m3 with one scale per tensor (max |x| maps to
    the format's largest finite value, 448), and back to float32. The
    gradient that flows back through it is rounded the same way, with a
    scale of its own, as fp8 training scales each operand."""
    import jax

    @jax.custom_vjp
    def q(x):
        return _fp8(x)

    q.defvjp(lambda x: (_fp8(x), None), lambda _, g: (_fp8(g),))
    return q(x)


def fp8_round(x):
    """`quant_fp8` without a gradient: for stored values."""
    return _fp8(x)


def bf16_round(x):
    import jax.numpy as jnp
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def identity(x):
    return x


def check_line(name: str, value: float, limit) -> dict:
    """One number compared with its limit; a limit of None marks a number
    that is read but not compared."""
    return {"name": name, "value": float(value),
            "limit": None if limit is None else float(limit)}


def all_within(checks: Sequence[dict]) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks if c["limit"] is not None)
