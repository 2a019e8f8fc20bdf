"""Work of one panel apply, ``out = Y^T v`` over the ``rows`` sampled rows
(primal: alpha += Y^T dw) or columns (dual: w -= Y da): the rows read once,
v read, the ``contraction``-long output written."""


def work(rows: int, contraction: int, itemsize: int) -> tuple[float, float]:
    """(operations, bytes) of one apply."""
    flops = 2 * rows * contraction
    nbytes = (rows * contraction + rows + contraction) * itemsize
    return float(flops), float(nbytes)
