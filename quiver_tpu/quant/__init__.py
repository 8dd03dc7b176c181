"""quiver_tpu.quant — quantized feature store (compressed hot/cold cache
with fused dequant-on-gather).

Pieces:

- ``codecs``: the codec registry (``fp32`` baseline, ``bf16`` cast,
  ``int8`` per-row affine) and the pluggable :class:`Codec` contract.
- ``QuantizedFeature``: the tiered store holding encoded rows in every
  tier (hot HBM prefix / ICI stripe / cold host tail), composed over the
  unchanged :class:`quiver_tpu.Feature`.
- ``lookup``: the in-jit fused paths — ``gather_dequant`` (resident
  tables), ``quantized_tiered_lookup`` (hot gather + encoded cold
  scatter, one decode), ``sharded_dequant_gather`` (encoded psum over
  ICI), ``make_quantized_train_step`` (drop-in for
  ``make_tiered_train_step``).

Byte/capacity accounting lives in
``quiver_tpu.parallel.scaling.quant_fetch_table``.
"""

from .codecs import (
    CODECS,
    Bf16Codec,
    Codec,
    Int8Codec,
    QuantizedRows,
    get_codec,
    register_codec,
)
from .feature import QuantizedFeature
from .lookup import (
    gather_dequant,
    make_quantized_train_step,
    quantized_tiered_lookup,
    sharded_dequant_gather,
)

__all__ = [
    "CODECS",
    "Bf16Codec",
    "Codec",
    "Int8Codec",
    "QuantizedFeature",
    "QuantizedRows",
    "gather_dequant",
    "get_codec",
    "make_quantized_train_step",
    "quantized_tiered_lookup",
    "register_codec",
    "sharded_dequant_gather",
]
