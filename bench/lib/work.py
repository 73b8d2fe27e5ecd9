"""Work of a round-0 greedy solve, counted from its shapes.

The count is the same whatever implements the solve (the XLA reference or
a Pallas kernel): it is what the algorithm needs, not what a program does.
Per machine of ``mu`` candidate rows against ``n_eval`` exemplars of width
``d``, picking ``k``:

  flops  2*mu*n_eval*d            candidate x exemplar contraction
       + 2*(mu + n_eval)*d        squared norms
       + 3*mu*n_eval              d2 = |x|^2 + |e|^2 - 2 x.e, clamp
       + k*3*mu*n_eval            per step: gain = sum max(cur - d2, 0)
       + k*3*n_eval*d             per step: the winner's distance refresh
  bytes  mu*d*4 + mu              the block (fp32) and its slot mask, once
       + k*(d + 1)*4              the selected rows and their ids
  + once per call: n_eval*d*4     the exemplars
"""
from __future__ import annotations


def greedy_round0(machines: int, mu: int, n_eval: int, d: int, k: int,
                  itemsize: int = 4) -> tuple[float, float]:
    per_flops = (2 * mu * n_eval * d + 2 * (mu + n_eval) * d
                 + 3 * mu * n_eval + k * 3 * mu * n_eval + k * 3 * n_eval * d)
    per_bytes = mu * d * itemsize + mu + k * (d + 1) * 4
    return (float(machines * per_flops),
            float(machines * per_bytes + n_eval * d * 4))


def roofline_share(flops: float, nbytes: float, device_s: float,
                   peak_flop_s: float, peak_bytes_s: float) -> tuple[float,
                                                                     str]:
    """Least time the chip could take over the time it took, in %, and
    which bound sets the least time."""
    t_flops, t_bytes = flops / peak_flop_s, nbytes / peak_bytes_s
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / device_s, bound
