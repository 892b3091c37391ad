"""The degree-bucketed Pallas intersect kernel (validated in interpret mode
on CPU), and the wedge-table machinery the XLA executors share.

``intersect.py``/``ops.py`` intersect oriented adjacency rows bucketed by
degree class; ``ref.py`` is their pure-jnp oracle.  ``wedge_common.py``
holds the chunk layout, padding policy and ranged-binary-search probe of
the support and peel-table builders in ``core/``.
"""

from repro.kernels.intersect import intersect_blocked
from repro.kernels.ops import compute_support_kernel
from repro.kernels.ref import intersect_ref

__all__ = ["intersect_blocked", "compute_support_kernel", "intersect_ref"]
