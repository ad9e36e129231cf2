"""Pallas TPU kernels for the D-com decomposer (validated in interpret
mode; ``lanczos_reorth`` also compiled for a TPU v5e by
``tests/test_tpu_compile.py``).

Kernels (one module each, ``ops`` wraps, ``ref`` is the jnp oracle):
* ``lanczos_reorth``  — fused matvec+CGS2 re-orthogonalization (paper Fig. 9)
* ``matvec_expand``   — expanded-reduction matvec (paper Fig. 12 primitive)
* ``lowrank_matmul``  — preserved-compute skinny GEMM (paper Eq. 6)
* ``outlier_extract`` — channel outlier statistics pass (paper §4)
* ``dkv_attention``   — flash-decoding through low-rank KV factors
                        (beyond-paper, EXPERIMENTS.md §Perf cell C)
* ``ssd_chunk``       — fused mamba2 intra-chunk SSD (decay tensor stays
                        in VMEM; beyond-paper, §Perf bonus)
"""
from . import ops, ref
from . import (dkv_attention, lanczos_reorth, lowrank_matmul, matvec_expand,
               outlier_extract, ssd_chunk)
