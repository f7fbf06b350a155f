"""Operations and bytes of the kernels and steps the cells time, from
unpadded shapes.

These are what the mathematics needs, not what the program issues: d and
the number of right-hand sides t are taken as they are, with none of the
lane padding the Pallas kernels add. A share of a roofline built from them
therefore counts padded work as waste.

One kernel entry K(x_i, x_j) applied to t right-hand sides costs
  2 d   MXU flops for the cross term <x_i, x_j> of the squared distance, and
  2 t   MXU flops for the contraction K @ V,
so `kernel_ops(rows, cols, d, t) = rows * cols * (2 d + 2 t)`. The VPU work
per entry (the norms, the square root and the exponential of the Matern
kernel) is left out: the roofline is the MXU's.

The bytes are the least a kernel must move through HBM: its inputs read
once and its output written once, in float32.
"""

from __future__ import annotations

F32 = 4


def kernel_ops(rows: int, cols: int, d: int, t: int) -> float:
    """MXU flops of K(rows, cols) @ V with V of t columns."""
    return float(rows) * float(cols) * (2.0 * d + 2.0 * t)


def kernel_bytes(rows: int, cols: int, d: int, t: int) -> float:
    """HBM bytes of K(rows, cols) @ V: both point sets and V read once, the
    (rows, t) result written once."""
    return float(F32) * (rows * d + cols * d + cols * t + rows * t)


def train_tile(n: int, mesh: tuple, mode: str) -> tuple[int, int]:
    """(rows, columns) of one chip's tile of the n x n kernel matrix on a
    (rows, columns) mesh: rows over the first axis and columns over the
    second in "2d"; rows over all chips and every column in "1d"."""
    r, c = mesh
    if mode == "2d":
        return n // r, n // c
    return n // (r * c), n


def train_traversals(mode: str, cg_iters: int) -> int:
    """Full passes over the n x n kernel matrix in one BBMM training step:
    `cg_iters` CG matvecs, one more to form the residual of a warm start
    (warm and refresh steps), and one for the Eq. 2 backward, which
    contracts the same entries against the solutions. `train_mfu` passes
    the iterations the step's solve needed; a fixed-trip loop executes
    its whole trip count, which `kmvm_traversals_per_step.train` reads
    from the device."""
    if mode not in ("cold", "warm", "refresh"):
        raise ValueError(f"unknown solve mode {mode!r}")
    return cg_iters + (0 if mode == "cold" else 1) + 1


def train_step_ops(n: int, d: int, num_probes: int, mode: str,
                   cg_iters: int) -> float:
    """MXU flops of one training step at n points: every traversal over
    t = 1 + num_probes right-hand sides (the targets and the probes)."""
    return train_traversals(mode, cg_iters) * kernel_ops(n, n, d,
                                                         1 + num_probes)


def predict_ops(rows: int, n: int, d: int, lanczos_rank: int) -> float:
    """MXU flops of serving `rows` query points against n training points:
    the mean's cross-covariance matvec (one column) and the variance's
    (lanczos_rank columns), each with its own distance pass."""
    return kernel_ops(rows, n, d, 1) + kernel_ops(rows, n, d, lanczos_rank)


def predict_bytes(rows: int, n: int, d: int, lanczos_rank: int) -> float:
    return kernel_bytes(rows, n, d, 1) + kernel_bytes(rows, n, d,
                                                      lanczos_rank)


def roofline_seconds(ops: float, nbytes: float, peak_flops: float,
                     peak_bytes_per_s: float) -> tuple[float, str]:
    """(least time, which bound binds): the larger of ops over peak FLOP/s
    and bytes over peak bandwidth."""
    t_ops, t_bytes = ops / peak_flops, nbytes / peak_bytes_per_s
    return (t_ops, "mxu") if t_ops >= t_bytes else (t_bytes, "hbm")
