"""Wire-format layer — how bits cross the network, BOTH directions.

The paper's entire communication story is the wire: a client uploads
``z ∈ {0,1}^n`` as *n bits*, and the server broadcasts the score
vector back.  This package makes both directions first-class, measured
subsystems:

 - ``bitpack``   — batched (K, n) <-> (K, ceil(n/32)) uint32 lane
   packing plus the packed-popcount reduction, composable with ``vmap``
   and with ``psum``/``all_gather`` inside ``shard_map``;
 - ``protocol``  — the ``Transport`` abstraction and the three
   interchangeable UPLINK aggregation strategies (``mean_f32``,
   ``psum_u32``, ``allgather_packed``), all bit-exact against each
   other;
 - ``downlink``  — the ``DownlinkCodec`` registry for the server's
   score broadcast (``f32`` identity oracle, ``u16``/``u8``
   probability-space quantizers whose widened-threshold draw is exact
   at the draw-word level);
 - ``metering``  — exact uplink AND downlink byte accounting per round
   per (transport, codec) (surfaced in round metrics, paper tables,
   benchmarks);
 - ``shardmap``  — entering ``shard_map`` over the context mesh
   (``jax.set_mesh``) and sizing mesh axes (shared with ``kernels``).
"""

from .bitpack import pack_mask, packed_len, packed_popcount_sum, unpack_mask
from .downlink import (
    DownlinkCodec,
    codec_for_dtype,
    codec_names,
    get_codec,
    register_codec,
)
from .metering import (
    downlink_table,
    mask_uplink_bytes,
    round_wire_report,
    score_downlink_bytes,
    streaming_peak_bytes,
    upload_slab_bytes,
    wire_table,
)
from .protocol import (
    Transport,
    get_transport,
    register_transport,
    resolve_transport,
    transport_names,
)
from .shardmap import axis_size, shard_map

__all__ = [
    "pack_mask", "packed_len", "packed_popcount_sum", "unpack_mask",
    "DownlinkCodec", "codec_for_dtype", "codec_names", "get_codec",
    "register_codec",
    "mask_uplink_bytes", "score_downlink_bytes", "round_wire_report",
    "upload_slab_bytes", "streaming_peak_bytes",
    "wire_table", "downlink_table",
    "Transport", "get_transport", "register_transport",
    "resolve_transport", "transport_names",
    "axis_size", "shard_map",
]
