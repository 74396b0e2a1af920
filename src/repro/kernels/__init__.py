"""Pallas TPU kernels for the core-set hot spots: compiled by Mosaic on a
TPU (``tests/test_tpu_compile.py`` compiles them for a described v5e),
interpreted on the CPU backend (``tests/test_kernels.py`` sweeps shapes and
dtypes against ``ref.py``)."""
from . import ops, ref
from .gmm_topb import gmm_topb_pallas
from .gmm_update import (gmm_grouped_topb_pallas, gmm_update_select_pallas,
                         resolve_interpret)
from .pairwise import pairwise_pallas

__all__ = ["ops", "ref", "gmm_update_select_pallas", "gmm_topb_pallas",
           "gmm_grouped_topb_pallas", "pairwise_pallas", "resolve_interpret"]
