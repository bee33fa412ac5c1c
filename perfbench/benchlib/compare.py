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
- `round_to(dtype)`, `e4m3_round(x)`, `quant_e4m3(x)`: the roundings a
  reference stores its parameters in and a control computes in, one
  precision below what a configuration states.
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


# By `lax.reduce_precision` and plain arithmetic, which the TPU's compiler
# keeps: inside a large jitted step it may keep a float32 -> bfloat16 ->
# float32 (or float8) `astype` round trip at float32 as excess precision,
# and on the chip it did so for the parameters a reference stores.
E4M3_MAX = 448.0              # largest finite float8_e4m3fn value
E4M3_MIN_NORMAL = 2.0 ** -6
E4M3_SUBNORMAL = 2.0 ** -9    # the spacing of its subnormals


def round_to(dtype):
    """-> a function rounding values to `dtype`'s exponent and mantissa
    bits, kept in float32."""
    import jax.numpy as jnp
    from jax import lax
    fi = jnp.finfo(dtype)
    return lambda x: lax.reduce_precision(
        x.astype(jnp.float32), exponent_bits=fi.nexp, mantissa_bits=fi.nmant)


def e4m3_round(x):
    """`x` rounded to float8 e4m3fn with one scale per tensor (max |x| maps
    to the format's largest finite value, 448), subnormals included, and
    kept in float32: what an `astype` round trip gives where it is kept."""
    import jax.numpy as jnp
    from jax import lax
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    y = x / s
    normal = lax.reduce_precision(y, exponent_bits=5, mantissa_bits=3)
    sub = jnp.round(y / E4M3_SUBNORMAL) * E4M3_SUBNORMAL
    return jnp.where(jnp.abs(y) < E4M3_MIN_NORMAL, sub, normal) * s


def quant_e4m3(x):
    """`e4m3_round` for a matmul operand: the gradient that flows back
    through it is rounded the same way, with a scale of its own, as fp8
    training scales each operand."""
    import jax

    @jax.custom_vjp
    def q(x):
        return e4m3_round(x)

    q.defvjp(lambda x: (e4m3_round(x), None), lambda _, g: (e4m3_round(g),))
    return q(x)


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
