"""The work a TNN gamma wave requires, computed from the configuration's
shapes: the yardstick of the roofline and utilization readers.

It counts what the algorithm needs, not what an implementation does:
padding, the ramp decomposition of the RNL sum and the float32 uniforms an
implementation draws its random bits from are all left out.

Operations, per image:
- forward: 2 per synapse per wave tick (compare-and-add of the ramp
  ``min(max(t - x, 0), w)`` into the body potential), ``2 * S * T``;
- STDP, when learning: 3 per synapse (the case selection and the up and
  down Bernoulli compares), plus 2 per synapse per wave for the counter
  apply (add, saturate).

Bytes, per wave of ``B`` images:
- spikes in, uint8: ``B * sites * p1``;
- weights read as int8 (``S`` bytes), and written again when learning;
- winner times out, uint8: every layer's when learning, only the last
  layer's when serving forward-only (``B * sites * q``).
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

FWD_OPS_PER_SYNAPSE_TICK = 2
STDP_OPS_PER_SYNAPSE_IMAGE = 3
APPLY_OPS_PER_SYNAPSE = 2


@dataclasses.dataclass(frozen=True)
class Work:
    ops: float
    bytes: float

    def times(self, n: float) -> "Work":
        return Work(self.ops * n, self.bytes * n)

    def least_s(self, peaks) -> float:
        """Least time the chip could take: the larger of the operations over
        the int8 peak and the bytes over the HBM bandwidth."""
        return max(self.ops / peaks["int8_ops_per_s"],
                   self.bytes / peaks["hbm_bytes_per_s"])


def layers(cfg) -> List[Tuple[int, int, int]]:
    """Per layer (sites, p, q)."""
    p = 2 * cfg["patch_k"] ** 2
    out = []
    for q in cfg["widths"]:
        out.append((cfg["sites"], p, q))
        p = q
    return out


def synapses(cfg) -> int:
    return sum(c * p * q for c, p, q in layers(cfg))


def wave(cfg, batch: int, learn: bool) -> Work:
    """Work of one gamma wave of ``batch`` images through the cascade."""
    T = 1 << cfg["time_bits"]
    S = synapses(cfg)
    ls = layers(cfg)
    ops = FWD_OPS_PER_SYNAPSE_TICK * S * T * batch
    nbytes = batch * ls[0][0] * ls[0][1] + S
    if learn:
        ops += STDP_OPS_PER_SYNAPSE_IMAGE * S * batch + APPLY_OPS_PER_SYNAPSE * S
        nbytes += S + sum(batch * c * q for c, _, q in ls)
    else:
        c, _, q = ls[-1]
        nbytes += batch * c * q
    return Work(float(ops), float(nbytes))
