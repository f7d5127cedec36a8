"""Pallas TPU kernels for the paper's compute hot spots.

The paper's dominant search cost is distance computation (Section 2.1) and
its flagship system optimization is computing distances *where the data
already lives* (in buffer-manager frames, Section 4.2.1). The TPU analogue:

  distance_matrix   -- MXU-tiled all-pairs distances (brute force /
                       retrieval_cand / construction pruning)
  gather_distance   -- fused gather+distance via scalar-prefetch BlockSpecs:
                       candidate rows stream HBM->VMEM and the distance is
                       computed in VMEM without materializing the gather
                       (the in-buffer-manager zero-copy optimization)
  bit_test          -- the second hop's eligibility: each lane's selected
                       set and visited set tested for all its two-hop ids
                       in one pass, both bitsets resident in VMEM
  quantized         -- int8-code distance with per-vector scales
                       (DiskANN-regime search, Section 5.8)
  segment_sum       -- CSR-sorted segment sum as one-hot MXU matmuls
                       (GNN message passing / EmbeddingBag hot path)

Each kernel has a pure-jnp oracle in ``ref.py`` and a jit'd public wrapper
in ``ops.py`` (which also routes to the oracle on hosts without a TPU,
keeping the kernels validated in interpret mode by the tests).
"""
