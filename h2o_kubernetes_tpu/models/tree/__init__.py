from .binning import BinSpec, apply_bins, fit_bins
from .core import Tree, TreeParams, predict_tree

__all__ = ["BinSpec", "apply_bins", "fit_bins", "Tree", "TreeParams",
           "predict_tree"]
