"""Core Barnes-Hut t-SNE library (the paper's contribution, in JAX)."""
from repro.core.morton import morton_encode, span_radius, DEFAULT_DEPTH
from repro.core.quadtree import build_quadtree, sort_points_by_code, LinearQuadtree
from repro.core.summarize import summarize, TreeSummary
from repro.core.repulsive import bh_repulsion_sorted, RepulsionResult
from repro.core.attractive import attractive_forces_ell
from repro.core.bsp import binary_search_perplexity, perplexity_of
from repro.core.knn import knn
from repro.core.tsne import (
    GradResult, IterationStats, NeighborGraph, TsneConfig, TsneResult,
    bh_gradient, init_state, preprocess, run_tsne, tsne_step,
)

__all__ = [
    "morton_encode", "span_radius", "DEFAULT_DEPTH",
    "build_quadtree", "sort_points_by_code", "LinearQuadtree",
    "summarize", "TreeSummary",
    "bh_repulsion_sorted", "RepulsionResult",
    "attractive_forces_ell",
    "binary_search_perplexity", "perplexity_of",
    "knn",
    "GradResult", "IterationStats", "NeighborGraph",
    "TsneConfig", "TsneResult", "run_tsne", "bh_gradient", "tsne_step",
    "preprocess", "init_state",
]
