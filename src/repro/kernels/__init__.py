"""Pallas TPU kernels for the integerized serving graph.

Layout:

- ``qmatmul.py``        reordered int8 matmul, fused dequant epilogue
                        (+ nibble-packed int4 weight variant)
- ``int_attention.py``  integer attention with embedded base-2 softmax:
                        two-pass baseline and the single-pass fused kernel
- ``pq_layernorm.py``   LayerNorm fused with the re-quantizer
- ``ref.py``            pure-jnp oracles (exact intended semantics)
- ``ops.py``            QTensor-typed wrappers (tests / benchmarks)
- ``dispatch.py``       backend selection: routes ``mode="int"`` model
                        graphs onto these kernels (``"pallas"`` on a TPU,
                        ``"xla"`` elsewhere; ``REPRO_KERNEL_BACKEND`` and
                        ``QuantConfig.backend`` override, per-op
                        shape-policy fallback)

The platform picks the kernel mode (:func:`interpret_mode`): compiled for
the MXU on a TPU, interpreted everywhere else.
"""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Whether a Pallas kernel runs in interpret mode.

    Never on a TPU.  Elsewhere the kernels are interpreted unless the caller
    passes ``interpret=False``, which compiles them for a TPU described
    without one being attached (compile-only tests).
    """
    if jax.default_backend() == "tpu":
        return False
    return True if interpret is None else interpret
