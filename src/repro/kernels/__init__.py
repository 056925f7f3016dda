"""Pallas TPU kernels + jnp oracles.

Every kernel resolves its backend automatically (see
:func:`repro.kernels.pearson_affinity.resolve_interpret`): Mosaic on TPU,
interpreter elsewhere, explicit override for tests.
"""
from repro.kernels.ops import (
    flash_attention_bhsd,
    pairwise_pearson_dissimilarity,
    ssd_scan,
)
from repro.kernels.pearson_affinity import resolve_interpret
