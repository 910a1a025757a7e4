"""FL message accounting (paper §3).

Four message kinds per round: s_msg_train (server -> clients, initial
weights), c_msg_train (client -> server, updated weights), s_msg_aggreg
(server -> clients, aggregated weights), c_msg_test (client -> server, ML
metrics). Byte sizes are measured from the *actual serialized payloads*,
and feed the Eq.-6 communication-cost model.

With wire compression (:mod:`repro.federated.compression`) the
``c_msg_train`` leg carries a quantized/sparsified delta: the log's
``c_msg_train_bytes`` is then the *wire* size (what the cost model must
see — compressed frames are what cross the inter-cloud link), while
``c_msg_train_dense_bytes`` keeps the dense fp32 equivalent so reports
can state the achieved compression ratio.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Union

import msgpack

from repro.checkpoint.serializer import pytree_num_bytes, serialize_pytree
from repro.core.application_model import MessageSizes
from repro.federated.compression import CompressionSpec, WireForm


@dataclasses.dataclass(frozen=True)
class RoundMessageLog:
    s_msg_train_bytes: int
    c_msg_train_bytes: int
    s_msg_aggreg_bytes: int
    c_msg_test_bytes: int
    # Wire-compression accounting: the codec the c_msg_train leg used
    # ("none" = raw fp32 pytree frames) and, when compressed, the dense
    # fp32 size the same update would have cost uncompressed.
    codec: str = "none"
    c_msg_train_dense_bytes: Optional[int] = None
    # Structured-update accounting: per-group wire and dense fp32 bytes
    # of the c_msg_train leg when clients ship named parameter groups
    # (None = unstructured round).  The sum of group_wire_bytes is the
    # structured frame's payload, so per-group ratios are first-class.
    group_wire_bytes: Optional[Dict[str, int]] = None
    group_dense_bytes: Optional[Dict[str, int]] = None

    def total_bytes(self, n_clients: int) -> int:
        """Bytes on the wire for a full round with n_clients."""
        return n_clients * (
            self.s_msg_train_bytes
            + self.c_msg_train_bytes
            + self.s_msg_aggreg_bytes
            + self.c_msg_test_bytes
        )

    @property
    def compression_ratio(self) -> Optional[float]:
        """dense / wire for the c_msg_train leg (None when uncompressed)."""
        if self.c_msg_train_dense_bytes is None or self.c_msg_train_bytes <= 0:
            return None
        return self.c_msg_train_dense_bytes / self.c_msg_train_bytes


def serialize_metrics(metrics: Dict[str, float]) -> bytes:
    """The wire form of a ``c_msg_test`` payload (msgpack, like weights)."""
    packed = msgpack.packb(
        {str(k): float(v) for k, v in metrics.items()}, use_bin_type=True
    )
    assert isinstance(packed, bytes)
    return packed


def measure_messages(
    params: Any,
    metrics_example: Dict[str, float],
    compression: Union[None, str, CompressionSpec] = None,
    schema: Any = None,
) -> RoundMessageLog:
    """Measure real serialized sizes for one round's message set.

    All four messages are measured from their actual serialized payloads
    — the metrics dict included, so Eq.-6 communication costs never mix
    measured weight transfers with a guessed per-key constant.  With
    ``compression`` the ``c_msg_train`` leg is the compressed frame size
    (exact: compressed frames are fixed-width given the element count),
    and the dense fp32 equivalent is reported alongside; the server->
    client legs always ship dense weights.

    With a ``schema`` (an :class:`~repro.federated.agg_engine.UpdateSchema`
    or a group mapping) the ``c_msg_train`` leg is a *structured* frame:
    only the named groups ride the wire, per-group byte maps fill
    ``group_wire_bytes``/``group_dense_bytes``, and the dense-equivalent
    stays the FULL model's fp32 size — the compression ratio then states
    what shipping groups instead of the whole pytree actually saved
    (e.g. the >= 50x of adapter-only federated LoRA)."""
    weight_bytes = len(serialize_pytree(params))
    metric_bytes = len(serialize_metrics(metrics_example))
    return RoundMessageLog(
        s_msg_train_bytes=weight_bytes,
        s_msg_aggreg_bytes=weight_bytes,
        c_msg_test_bytes=metric_bytes,
        **WireForm(compression, schema).message_sizes(params, weight_bytes),
    )


def to_cost_model_sizes(log: RoundMessageLog) -> MessageSizes:
    """Bridge real measured sizes into the scheduler's cost model.

    Always the *wire* sizes — with compression enabled the c_msg_train
    term is the compressed frame, which is what the inter-cloud link
    actually carries (the dense equivalent stays a reporting-only
    field)."""
    return MessageSizes(
        s_msg_train_gb=log.s_msg_train_bytes / 1e9,
        s_msg_aggreg_gb=log.s_msg_aggreg_bytes / 1e9,
        c_msg_train_gb=log.c_msg_train_bytes / 1e9,
        c_msg_test_gb=log.c_msg_test_bytes / 1e9,
    )


def model_weight_bytes(params: Any) -> int:
    return int(pytree_num_bytes(params))
