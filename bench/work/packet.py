"""Work of one Gram packet as the algorithm needs it: the ``rows`` sampled
rows (primal) or columns (dual) of length ``contraction``, each read once,
and u; G (rows x rows) and r written.  Operations: the symmetric Gram,
rows (rows + 1) / 2 dot products of length ``contraction``, plus the
residual r = Y u.  Whatever implements the kernel, this stays the count, so
a kernel that reads more than this shows as a lower roofline share."""


def work(rows: int, contraction: int, itemsize: int) -> tuple[float, float]:
    """(operations, bytes) of one packet."""
    flops = rows * (rows + 1) * contraction + 2 * rows * contraction
    nbytes = (rows * contraction + contraction + rows * rows + rows) * itemsize
    return float(flops), float(nbytes)
