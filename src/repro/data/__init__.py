from .regression import SyntheticSpec, make_regression, PAPER_DATASETS

__all__ = ["SyntheticSpec", "make_regression", "PAPER_DATASETS"]
