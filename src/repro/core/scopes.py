"""Names of the descent step's layers.

Each layer of ``tsne_step`` runs under one ``jax.named_scope`` named here.
A scope is HLO metadata only: it lands in every op's ``op_name`` path, in
the lowered and the compiled module, and leaves the program and its
fusions as they were.  A profile's device ops are read back to layers by
the innermost of these names in their ``op_name``.
"""
from __future__ import annotations

BH_TREE = "bh_tree"             # span radius, Morton encode, sort, quadtree
BH_SUMMARIZE = "bh_summarize"   # per-node counts, centres of mass, sides
BH_TRAVERSAL = "bh_traversal"   # the lockstep walk and its scatter to point order
ATTRACTIVE = "attractive"       # the sparse P-weighted term, every layout
UPDATE = "update"               # force combination, grad norm, momentum/gains
FFT_SPREAD = "fft_spread"       # charges onto the interpolation lattice
FFT_CONVOLVE = "fft_convolve"   # kernel convolution by FFT
FFT_GATHER = "fft_gather"       # potentials back at the points, the forces

STEP_SCOPES = (BH_TREE, BH_SUMMARIZE, BH_TRAVERSAL, ATTRACTIVE, UPDATE,
               FFT_SPREAD, FFT_CONVOLVE, FFT_GATHER)
