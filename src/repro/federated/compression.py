"""Client-side update compression for the c_msg_train wire path.

Real inter-cloud WAN links (the paper's AWS<->GCP deployment, §5) give a
few percent of loopback throughput, so wire bytes — not server compute —
dominate the Eq.-7 communication term.  This module compresses each
client's *delta* against the round's global weights before it is
serialized into a transport frame:

  ``int8``  — symmetric per-block quantization (block = the Pallas
              ``BLOCK`` of :mod:`repro.kernels.fedavg_reduce`, so each
              wire scale maps 1:1 onto one kernel grid tile);
              ~3.98x smaller than fp32.
  ``fp16``  — half-precision cast; 2x smaller, near-lossless.
  ``topk``  — magnitude top-k sparsification (k = ``k_frac`` of the
              elements); int32 indices + fp16 values, ~6.7x smaller at
              the default ``k_frac=0.1``.

Deltas rather than raw parameters for two reasons: the weighted average
``g + sum(w_i * d_i) / W`` is *exactly* the plain FedAvg of the raw
parameters (the base cancels), and deltas are the small-magnitude signal
that quantization and top-k preserve well.  Per-client error-feedback
residuals (:class:`ClientCompressor`) carry whatever a codec dropped into
the next round's delta, which is what preserves convergence under
aggressive sparsification.

The server side never materializes a dense fp32 update: the
:class:`~repro.federated.agg_engine.StreamingAggregator` folds
:class:`CompressedUpdate` payloads straight into its fp32 accumulator via
the fused Pallas dequantize-and-fold kernel (``dequant_fold``).

:class:`WireForm` decides, once per run, which of these forms a
``c_msg_train`` takes (dense, compressed, or structured per-group) and
answers for it everywhere an update is encoded, sent, decoded, folded,
parked or accounted for.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import msgpack
import numpy as np

from repro import spans
from repro.checkpoint.serializer import (
    BytesLike,
    DeserializationError,
    deserialize_pytree,
    serialize_pytree,
)

# One quantization block per Pallas grid tile of the fused
# dequantize-and-fold kernel (kernels/fedavg_reduce.BLOCK), so the (B,)
# scale vector on the wire feeds the kernel's per-tile scale ref directly.
QBLOCK: int = 8 * 128 * 8

CODECS: Tuple[str, ...] = ("int8", "fp16", "topk")

_WIRE_VERSION = 1


@dataclasses.dataclass(frozen=True)
class CompressionSpec:
    """Validated compression configuration (builder knob payload).

    ``codec`` is one of :data:`CODECS`; ``k_frac`` only applies to
    ``topk`` (fraction of elements kept, in (0, 1]); ``error_feedback``
    enables the per-client residual buffer (recommended — required for
    top-k convergence).
    """

    codec: str
    k_frac: float = 0.1
    error_feedback: bool = True

    def __post_init__(self) -> None:
        if self.codec not in CODECS:
            raise ValueError(
                f"unknown compression codec {self.codec!r}; expected one of {CODECS}"
            )
        if not (0.0 < self.k_frac <= 1.0):
            raise ValueError(
                f"topk k_frac must be in (0, 1], got {self.k_frac}"
            )


def parse_compression(
    spec: Union[None, str, CompressionSpec],
) -> Optional[CompressionSpec]:
    """Coerce a user-facing compression knob into a :class:`CompressionSpec`.

    Accepts ``None`` (off), an existing spec, or a string: ``"int8"``,
    ``"fp16"``, ``"topk"``, or ``"topk:0.05"`` (explicit kept fraction).
    Raises ``ValueError`` on anything else — the builder calls this at
    configuration time so bad knobs fail before any round runs.
    """
    if spec is None:
        return None
    if isinstance(spec, CompressionSpec):
        return spec
    if not isinstance(spec, str):
        raise ValueError(
            f"compression must be None, a codec string, or a CompressionSpec; "
            f"got {type(spec).__name__}"
        )
    name, _, arg = spec.partition(":")
    name = name.strip().lower()
    if arg:
        if name != "topk":
            raise ValueError(
                f"only the topk codec takes a parameter, got {spec!r}"
            )
        try:
            k_frac = float(arg)
        except ValueError as exc:
            raise ValueError(f"bad topk fraction in {spec!r}") from exc
        return CompressionSpec(codec="topk", k_frac=k_frac)
    return CompressionSpec(codec=name)


def topk_count(total_elems: int, k_frac: float) -> int:
    """Number of elements a top-k codec keeps (at least 1)."""
    return max(1, int(round(total_elems * k_frac)))


@dataclasses.dataclass(frozen=True)
class CompressedUpdate:
    """One client's compressed delta, as carried on the wire.

    ``data`` holds the quantized payload (int8 codes, fp16 values, or the
    fp16 top-k values); ``scales`` the per-:data:`QBLOCK` fp32
    dequantization scales (int8 only); ``indices`` the sorted int32
    element indices (topk only).  ``total_elems`` is the dense length the
    update folds into — the aggregator validates it against the model's
    ravel plan.

    ``base_round`` tags which round's global weights the delta was taken
    against.  A delta is only meaningful relative to that exact base, so
    a tagged update lets the server-side aggregator reject a fold
    against any other round's weights (the stale-base reuse bug) instead
    of silently corrupting the average.  ``None`` means untagged
    (legacy encoders); untagged updates fold without the check.
    """

    codec: str
    total_elems: int
    data: np.ndarray
    scales: Optional[np.ndarray] = None
    indices: Optional[np.ndarray] = None
    base_round: Optional[int] = None

    @property
    def wire_bytes(self) -> int:
        """Serialized frame size (what actually crosses the transport)."""
        return len(serialize_update(self))

    @property
    def dense_bytes(self) -> int:
        """Dense fp32 equivalent (what an uncompressed frame would carry)."""
        return self.total_elems * 4


def _num_blocks(total_elems: int) -> int:
    return -(-total_elems // QBLOCK)


def _quantize(x: Any, codec: str) -> Tuple[Any, Any, Any]:
    """The quantizer: ``(nb, QBLOCK)`` fp32 blocks to the codec's codes,
    its per-block scales (``None`` for ``fp16``) and the dequantized
    blocks.  ``int8`` is symmetric per block, ``scale = absmax / 127``,
    round half to even, an all-zero block coded 0."""
    if codec == "fp16":
        data = x.astype(jnp.float16)
        return data, None, data.astype(jnp.float32)
    # The barrier keeps 127 from being a literal, which the compiler
    # would turn into a multiply by its rounded reciprocal: the scale
    # stays the divided absmax / 127 of the wire format's definition.
    scales = jnp.max(jnp.abs(x), axis=1) / jax.lax.optimization_barrier(jnp.float32(127.0))
    safe = jnp.where(scales > 0.0, scales, jnp.float32(1.0))
    q = jnp.clip(jnp.round(x / safe[:, None]), -127.0, 127.0)
    q = jnp.where((scales == 0.0)[:, None], 0.0, q).astype(jnp.int8)
    return q, scales, q.astype(jnp.float32) * scales[:, None]


def _blocks(flat: Any) -> Any:
    """A flat fp32 vector zero-padded to whole ``(nb, QBLOCK)`` blocks."""
    n = flat.shape[0]
    return jnp.pad(flat, (0, _num_blocks(n) * QBLOCK - n)).reshape(-1, QBLOCK)


@functools.partial(jax.jit, static_argnames="codec")
def _quantize_flat(flat: Any, codec: str) -> Tuple[Any, Any]:
    data, scales, _ = _quantize(_blocks(flat), codec)
    return data.reshape(-1)[: flat.shape[0]], scales


@functools.partial(jax.jit, static_argnames="codec", donate_argnames="residual")
def _encode_update(
    global_params: Any, local_params: Any, residual: Any, codec: str
) -> Tuple[Any, Any, Any]:
    """One client's update encoded on its device: ``local - global`` per
    leaf (never two whole flat vectors at once), flattened and padded to
    blocks, plus the error-feedback ``residual`` (``None``: none kept),
    quantized.  Returns the codes of the ``n`` elements, the scales and
    the new residual ``x - dequantized`` (``None`` without one)."""
    deltas = [
        jnp.ravel(loc.astype(jnp.float32) - glob.astype(jnp.float32))
        for glob, loc in zip(jax.tree.leaves(global_params), jax.tree.leaves(local_params))
    ]
    flat = jnp.concatenate(deltas)
    x = _blocks(flat)
    if residual is not None:
        x = x + residual
    data, scales, deq = _quantize(x, codec)
    return (
        data.reshape(-1)[: flat.shape[0]],
        scales,
        None if residual is None else x - deq,
    )


def compress(
    flat: np.ndarray,
    spec: CompressionSpec,
    base_round: Optional[int] = None,
) -> CompressedUpdate:
    """Compress a dense fp32 vector (a flattened delta) with ``spec``.

    Deterministic, so the virtual-clock server and the live socket
    workers produce bit-identical updates for the same inputs
    (trace/params parity across bus drivers).  ``int8`` and ``fp16``
    run the device quantizer that :class:`ClientCompressor` runs;
    ``topk`` selects on the host.  ``base_round`` tags the update with
    the round whose global weights the delta was taken against (see
    :class:`CompressedUpdate`).
    """
    vec = np.ascontiguousarray(np.asarray(flat, dtype=np.float32).reshape(-1))
    n = int(vec.size)
    if n == 0:
        raise ValueError("cannot compress an empty update")

    if spec.codec == "topk":
        k = topk_count(n, spec.k_frac)
        if k >= n:
            idx = np.arange(n, dtype=np.int32)
        else:
            idx = np.sort(
                np.argpartition(np.abs(vec), n - k)[n - k:]
            ).astype(np.int32)
        return CompressedUpdate(
            codec="topk",
            total_elems=n,
            data=vec[idx].astype(np.float16),
            indices=idx,
            base_round=base_round,
        )

    data, scales = _quantize_flat(jnp.asarray(vec), spec.codec)
    return CompressedUpdate(
        codec=spec.codec, total_elems=n, data=np.asarray(data),
        scales=None if scales is None else np.asarray(scales),
        base_round=base_round,
    )


def decompress(update: CompressedUpdate) -> np.ndarray:
    """Dense fp32 reconstruction (reference path; the server-side fold
    uses the fused kernel instead and never calls this per round)."""
    n = update.total_elems
    out = np.zeros(n, dtype=np.float32)
    if update.codec == "fp16":
        out[:] = update.data.astype(np.float32)
    elif update.codec == "topk":
        assert update.indices is not None
        out[update.indices] = update.data.astype(np.float32)
    else:
        assert update.scales is not None
        nb = _num_blocks(n)
        padded = np.zeros(nb * QBLOCK, dtype=np.float32)
        padded[:n] = update.data.astype(np.float32)
        deq = padded.reshape(nb, QBLOCK) * update.scales[:, None]
        out[:] = deq.reshape(-1)[:n]
    return out


def materialize_update(base: Any, update: CompressedUpdate) -> Any:
    """Dense pytree equivalent of ``base + decompress(update)``.

    A compressed update is a delta against one specific round's global
    weights; anything that outlives that round — above all a
    :class:`~repro.federated.agg_engine.CarryEntry` parked for a later
    round's fold — must be pinned to dense parameters *while the origin
    base is still on hand*.  Folding the raw ``CompressedUpdate`` into a
    later round's aggregator would apply the delta to the wrong base and
    silently corrupt the average.
    """
    from repro.federated.agg_engine import plan_for

    plan = plan_for(base)
    if update.total_elems != plan.total_elems:
        raise ValueError(
            f"compressed update has {update.total_elems} elements; "
            f"the base has {plan.total_elems}"
        )
    vec = np.asarray(plan.flatten(base), dtype=np.float32) + decompress(update)
    return plan.unflatten(vec)


# ---------------------------------------------------------------------------
# Wire form: one msgpack blob per update, embedded as a frame payload
# ---------------------------------------------------------------------------

def _update_obj(update: CompressedUpdate) -> Dict[str, Any]:
    """The msgpack-able dict form of one compressed update (shared by the
    whole-model frame and each group of a structured frame)."""
    obj: Dict[str, Any] = {
        "v": _WIRE_VERSION,
        "codec": update.codec,
        "n": int(update.total_elems),
        "data": update.data.tobytes(),
    }
    if update.scales is not None:
        obj["scales"] = np.ascontiguousarray(update.scales, np.float32).tobytes()
    if update.indices is not None:
        obj["idx"] = np.ascontiguousarray(update.indices, np.int32).tobytes()
    if update.base_round is not None:
        obj["br"] = int(update.base_round)
    return obj


def serialize_update(update: CompressedUpdate) -> bytes:
    """msgpack wire form of a compressed update (a c_msg_train payload).
    Counter ``pack_s`` (``tobytes`` and ``packb``)."""
    with spans.span("fl.serialize") as sp:
        t0 = time.perf_counter()
        packed = msgpack.packb(_update_obj(update), use_bin_type=True)
        spans.add("pack_s", time.perf_counter() - t0)
        assert isinstance(packed, bytes)
        sp.nbytes = len(packed)
    return packed


def deserialize_update(payload: BytesLike) -> CompressedUpdate:
    """Decode a compressed c_msg_train payload (any bytes-like object,
    read in place).

    Raises :class:`~repro.checkpoint.serializer.DeserializationError` on
    any malformed, truncated, or internally inconsistent frame — the same
    typed error the dense path raises, so the transport's corrupt-frame
    re-request recovery (§4.3) applies unchanged to compressed frames.
    Counter ``unpack_s`` (``unpackb`` and ``frombuffer``); the payload
    stays on the host until the fold moves it.
    """
    with spans.span("fl.deserialize", nbytes=len(payload)):
        t0 = time.perf_counter()
        try:
            obj = msgpack.unpackb(payload, raw=False)
        except Exception as exc:
            raise DeserializationError(
                f"malformed compressed update frame: {exc}"
            ) from exc
        if not isinstance(obj, dict):
            raise DeserializationError("compressed update frame is not a map")
        update = _decode_update_obj(obj)
        spans.add("unpack_s", time.perf_counter() - t0)
        return update


def _decode_update_obj(obj: Dict[str, Any]) -> CompressedUpdate:
    """Validate + decode one update obj (see :func:`_update_obj`)."""
    if obj.get("v") != _WIRE_VERSION:
        raise DeserializationError(
            f"unsupported compressed update version {obj.get('v')!r}"
        )
    codec = obj.get("codec")
    if codec not in CODECS:
        raise DeserializationError(f"unknown codec {codec!r} in update frame")
    n = obj.get("n")
    if not isinstance(n, int) or n <= 0:
        raise DeserializationError(f"bad element count {n!r} in update frame")
    raw = obj.get("data")
    if not isinstance(raw, (bytes, bytearray)):
        raise DeserializationError("compressed update frame has no data field")
    base_round = obj.get("br")
    if base_round is not None and not isinstance(base_round, int):
        raise DeserializationError(
            f"bad base round tag {base_round!r} in update frame"
        )

    if codec == "fp16":
        if len(raw) != 2 * n:
            raise DeserializationError(
                f"fp16 payload length {len(raw)} != 2 * {n}"
            )
        data = np.frombuffer(raw, dtype=np.float16)
        return CompressedUpdate(
            codec="fp16", total_elems=n, data=data, base_round=base_round
        )

    if codec == "topk":
        rawi = obj.get("idx")
        if not isinstance(rawi, (bytes, bytearray)):
            raise DeserializationError("topk update frame has no index field")
        if len(rawi) % 4 or len(raw) != 2 * (len(rawi) // 4):
            raise DeserializationError(
                f"topk payload lengths inconsistent: {len(raw)}B values, "
                f"{len(rawi)}B indices"
            )
        idx = np.frombuffer(rawi, dtype=np.int32)
        if idx.size == 0 or idx.size > n:
            raise DeserializationError(f"topk index count {idx.size} out of range")
        if int(idx[0]) < 0 or int(idx[-1]) >= n or np.any(np.diff(idx) <= 0):
            raise DeserializationError("topk indices not sorted within range")
        data = np.frombuffer(raw, dtype=np.float16)
        return CompressedUpdate(
            codec="topk", total_elems=n, data=data, indices=idx,
            base_round=base_round,
        )

    # int8
    raws = obj.get("scales")
    if not isinstance(raws, (bytes, bytearray)):
        raise DeserializationError("int8 update frame has no scales field")
    if len(raw) != n:
        raise DeserializationError(f"int8 payload length {len(raw)} != {n}")
    if len(raws) != 4 * _num_blocks(n):
        raise DeserializationError(
            f"int8 scale length {len(raws)} != 4 * {_num_blocks(n)} blocks"
        )
    data = np.frombuffer(raw, dtype=np.int8)
    scales = np.frombuffer(raws, dtype=np.float32)
    return CompressedUpdate(
        codec="int8", total_elems=n, data=data, scales=scales,
        base_round=base_round,
    )


def compressed_wire_bytes(
    total_elems: int, spec: CompressionSpec, base_round: Optional[int] = None
) -> int:
    """Serialized c_msg_train size for a model of ``total_elems`` weights
    (tagged with ``base_round`` when given).

    Compressed frame sizes are data-independent given the element count
    (fixed-width codes plus msgpack framing), so message accounting can
    report exact wire bytes without compressing real data.
    """
    zeros = np.zeros(total_elems, dtype=np.float32)
    return len(serialize_update(compress(zeros, spec, base_round=base_round)))


# ---------------------------------------------------------------------------
# Structured updates: named parameter groups on the wire
# ---------------------------------------------------------------------------

# A group's wire payload is either raw fp32 *values* (an np.ndarray — the
# group's current parameters, used when the group needs no codec) or a
# CompressedUpdate *delta* against the group's slice of the round base.
GroupPayload = Union[np.ndarray, CompressedUpdate]


@dataclasses.dataclass(frozen=True)
class StructuredUpdate:
    """One client's structured ``c_msg_train``: named per-group payloads.

    Only the groups the client trained ride the wire — a federated-LoRA
    client ships just its ``adapters`` group, orders of magnitude fewer
    bytes than the dense model.  ``schema_signature`` pins the exact
    (model structure x group partition) the payloads were encoded under;
    the structured aggregator refuses a fold under any other schema.
    ``base_round`` tags the round whose global weights compressed group
    deltas were taken against (raw-value groups are base-independent).
    """

    groups: Tuple[Tuple[str, GroupPayload], ...]
    schema_signature: str
    base_round: Optional[int] = None

    @property
    def wire_bytes(self) -> int:
        """Serialized frame size (what actually crosses the transport)."""
        return len(serialize_structured(self))

    @property
    def dense_bytes(self) -> int:
        """Dense fp32 equivalent of the *shipped* groups only."""
        return sum(self.group_dense_bytes().values())

    def group_wire_bytes(self) -> Dict[str, int]:
        """Per-group serialized payload sizes (RoundMessageLog accounting)."""
        out: Dict[str, int] = {}
        for name, payload in self.groups:
            packed = msgpack.packb(_group_obj(payload), use_bin_type=True)
            assert isinstance(packed, bytes)
            out[name] = len(packed)
        return out

    def group_dense_bytes(self) -> Dict[str, int]:
        """Per-group dense fp32 equivalents."""
        return {
            name: (payload.dense_bytes
                   if isinstance(payload, CompressedUpdate)
                   else int(np.asarray(payload).size) * 4)
            for name, payload in self.groups
        }

    def group_names(self) -> Tuple[str, ...]:
        return tuple(n for n, _ in self.groups)


def _group_obj(payload: GroupPayload) -> Dict[str, Any]:
    if isinstance(payload, CompressedUpdate):
        return _update_obj(payload)
    vec = np.ascontiguousarray(np.asarray(payload, np.float32).reshape(-1))
    return {"raw": vec.tobytes(), "n": int(vec.size)}


def serialize_structured(update: StructuredUpdate) -> bytes:
    """msgpack wire form of a structured update (a c_msg_train payload)."""
    obj: Dict[str, Any] = {
        "v": _WIRE_VERSION,
        "structured": 1,
        "sig": update.schema_signature,
        "groups": [[name, _group_obj(p)] for name, p in update.groups],
    }
    if update.base_round is not None:
        obj["br"] = int(update.base_round)
    packed = msgpack.packb(obj, use_bin_type=True)
    assert isinstance(packed, bytes)
    return packed


def deserialize_structured(payload: BytesLike) -> StructuredUpdate:
    """Decode a structured c_msg_train payload (any bytes-like object,
    read in place; typed errors, like :func:`deserialize_update`, so
    §4.3 re-request recovery applies)."""
    try:
        obj = msgpack.unpackb(payload, raw=False)
    except Exception as exc:
        raise DeserializationError(
            f"malformed structured update frame: {exc}"
        ) from exc
    if not isinstance(obj, dict) or obj.get("structured") != 1:
        raise DeserializationError("not a structured update frame")
    if obj.get("v") != _WIRE_VERSION:
        raise DeserializationError(
            f"unsupported structured update version {obj.get('v')!r}"
        )
    sig = obj.get("sig")
    if not isinstance(sig, str) or not sig:
        raise DeserializationError("structured update frame has no schema tag")
    base_round = obj.get("br")
    if base_round is not None and not isinstance(base_round, int):
        raise DeserializationError(
            f"bad base round tag {base_round!r} in structured frame"
        )
    raw_groups = obj.get("groups")
    if not isinstance(raw_groups, list) or not raw_groups:
        raise DeserializationError("structured update frame has no groups")
    groups: List[Tuple[str, GroupPayload]] = []
    for entry in raw_groups:
        if (not isinstance(entry, (list, tuple)) or len(entry) != 2
                or not isinstance(entry[0], str)
                or not isinstance(entry[1], dict)):
            raise DeserializationError(
                "structured update group entry is not [name, payload]"
            )
        name, sub = entry
        if "raw" in sub:
            raw = sub.get("raw")
            n = sub.get("n")
            if not isinstance(raw, (bytes, bytearray)):
                raise DeserializationError(
                    f"group {name!r} raw payload is not bytes"
                )
            if not isinstance(n, int) or n <= 0 or len(raw) != 4 * n:
                raise DeserializationError(
                    f"group {name!r} raw payload length {len(raw)} != 4 * {n!r}"
                )
            groups.append((name, np.frombuffer(raw, dtype=np.float32)))
        else:
            groups.append((name, _decode_update_obj(sub)))
    return StructuredUpdate(
        groups=tuple(groups), schema_signature=sig, base_round=base_round
    )


def materialize_structured(
    base: Any, update: StructuredUpdate, schema: Any
) -> Dict[str, np.ndarray]:
    """Base-independent raw-values form of a structured update.

    The structured analogue of :func:`materialize_update` for carry-over
    parking: compressed group deltas only mean something against their
    origin round's base, so a parked update is pinned to per-group raw
    *values* while that base is still on hand.  Returns a plain
    ``{group: fp32 vector}`` mapping the structured aggregator folds in
    any later round."""
    resolved = schema if hasattr(schema, "plan") else schema.resolve(base)
    if update.schema_signature != resolved.signature:
        raise ValueError(
            f"structured update was encoded under schema "
            f"{update.schema_signature}, not {resolved.signature}"
        )
    out: Dict[str, np.ndarray] = {}
    for name, payload in update.groups:
        gp = resolved.group(name)
        if isinstance(payload, CompressedUpdate):
            if payload.total_elems != gp.total_elems:
                raise ValueError(
                    f"group {name!r} update has {payload.total_elems} "
                    f"elements; the group has {gp.total_elems}"
                )
            g = np.asarray(gp.flatten(base), dtype=np.float32)
            out[name] = g + decompress(payload)
        else:
            out[name] = np.asarray(payload, dtype=np.float32)
    return out


# ---------------------------------------------------------------------------
# Client-side encoder with error feedback
# ---------------------------------------------------------------------------

class ClientCompressor:
    """Per-client delta encoder with an error-feedback residual.

    Each round the client compresses ``delta = local - global`` *plus*
    whatever earlier rounds' codecs dropped (``residual``), then stores
    the new quantization error for the next round:

        e_t   = delta_t + residual_{t-1}
        u_t   = compress(e_t)
        residual_t = e_t - decompress(u_t)

    ``int8`` and ``fp16`` run all of it as one program on the device the
    weights live on, and the residual stays there, padded to whole
    :data:`QBLOCK` blocks: only the codes and scales come to the host.
    ``topk`` selects on the host, with a host residual.

    The residual lives with the client (worker) — a restarted or replaced
    worker starts with a zero residual, which only costs a little extra
    compression error on its next update, never correctness.
    """

    def __init__(self, spec: CompressionSpec) -> None:
        self.spec = spec
        self._residual: Any = None

    def encode(
        self,
        global_params: Any,
        local_params: Any,
        base_round: Optional[int] = None,
    ) -> CompressedUpdate:
        """Compress this round's update against the round's global weights.

        ``base_round`` tags the update with the round those globals
        belong to, so the aggregator can refuse to fold it against any
        other base (see :class:`CompressedUpdate`).  Counters ``d2h_s``/
        ``d2h_bytes``: the copy to the host of the codes and scales
        (``topk``: of the two flattened weight vectors); ``encode_device``
        counts the encodes run on the device."""
        with spans.span("fl.encode") as sp:
            if self.spec.codec == "topk":
                update = self._encode_on_host(global_params, local_params, base_round)
            else:
                update = self._encode_on_device(global_params, local_params, base_round)
            sp.nbytes = update.dense_bytes
        return update

    def _encode_on_device(
        self, global_params: Any, local_params: Any, base_round: Optional[int]
    ) -> CompressedUpdate:
        if self.spec.error_feedback and self._residual is None:
            n = sum(int(np.size(leaf)) for leaf in jax.tree.leaves(global_params))
            self._residual = jnp.zeros((_num_blocks(n), QBLOCK), jnp.float32)
        data, scales, residual = _encode_update(
            global_params, local_params, self._residual, codec=self.spec.codec
        )
        self._residual = residual
        t0 = time.perf_counter()
        data = np.asarray(data)
        scales = None if scales is None else np.asarray(scales)
        spans.add("d2h_s", time.perf_counter() - t0)
        spans.add("d2h_bytes", data.nbytes + (0 if scales is None else scales.nbytes))
        spans.add("encode_device", 1)
        return CompressedUpdate(
            codec=self.spec.codec, total_elems=int(data.size), data=data,
            scales=scales, base_round=base_round,
        )

    def _encode_on_host(
        self, global_params: Any, local_params: Any, base_round: Optional[int]
    ) -> CompressedUpdate:
        from repro.federated.agg_engine import plan_for

        plan = plan_for(global_params)
        t0 = time.perf_counter()
        g = np.asarray(plan.flatten(global_params), dtype=np.float32)
        p = np.asarray(plan.flatten(local_params), dtype=np.float32)
        spans.add("d2h_s", time.perf_counter() - t0)
        spans.add("d2h_bytes", g.nbytes + p.nbytes)
        delta = p - g
        if self.spec.error_feedback and self._residual is not None:
            delta = delta + self._residual
        update = compress(delta, self.spec, base_round=base_round)
        if self.spec.error_feedback:
            self._residual = delta - decompress(update)
        return update

    def reset(self) -> None:
        self._residual = None


class StructuredCompressor:
    """Per-client structured encoder: one payload per schema group.

    Without a codec each group ships its raw fp32 *values* (already a
    huge win when the schema selects a small group like LoRA adapters);
    with a :class:`CompressionSpec` each group's *delta* against the
    round base is encoded by its own :class:`ClientCompressor` over that
    group's leaves, so ``int8``/``fp16`` groups run the one device
    program and keep their residuals on the device (a group the client
    skips a round keeps its residual — nothing is dropped).

    The schema is resolved lazily against the first round's global
    weights and the resolution cached by plan signature, so repeated
    rounds over the same structure pay nothing.
    """

    def __init__(self, schema: Any, spec: Union[None, str, CompressionSpec] = None) -> None:
        self.form = WireForm(spec, schema)
        if self.form.schema is None:
            raise ValueError("StructuredCompressor needs a schema")
        self._groups: Dict[str, ClientCompressor] = {}

    def encode(
        self,
        global_params: Any,
        local_params: Any,
        base_round: Optional[int] = None,
    ) -> StructuredUpdate:
        """Encode the groups of this round's update (all schema groups)."""
        resolved = self.form.resolve(global_params)
        spec = self.form.spec
        if spec is None:
            return StructuredUpdate(
                groups=tuple(
                    (name, np.asarray(gp.flatten(local_params), dtype=np.float32))
                    for name, gp in resolved.groups
                ),
                schema_signature=resolved.signature,
            )
        glob, loc = jax.tree.leaves(global_params), jax.tree.leaves(local_params)
        groups: List[Tuple[str, GroupPayload]] = []
        for name, gp in resolved.groups:
            comp = self._groups.setdefault(name, ClientCompressor(spec))
            groups.append((name, comp.encode(
                [glob[i] for i in gp.leaf_indices],
                [loc[i] for i in gp.leaf_indices],
                base_round=base_round,
            )))
        return StructuredUpdate(
            groups=tuple(groups),
            schema_signature=resolved.signature,
            base_round=base_round,
        )

    def reset(self) -> None:
        self._groups = {}


# ---------------------------------------------------------------------------
# The run's wire form: the one place that decides an update's encoding
# ---------------------------------------------------------------------------

class WireForm:
    """How one run's ``c_msg_train`` carries a client's update.

    Decided once from the run's ``(compression, schema)`` pair: a dense
    fp32 pytree frame (neither), one compressed delta against the
    round's global weights (``compression``), or the schema's named
    groups (``schema``; each group a compressed delta when
    ``compression`` is set too, else its raw fp32 values).  The worker
    that encodes and sends a reply, the driver that decodes it, the
    engine that folds or parks it and the message accounting all ask
    this object instead of branching on the pair themselves.
    """

    def __init__(
        self,
        compression: Union[None, str, CompressionSpec] = None,
        schema: Any = None,
    ) -> None:
        from repro.federated.agg_engine import as_update_schema

        self.spec = parse_compression(compression)
        self.schema = as_update_schema(schema)
        self._resolved: Any = None

    @property
    def against_base(self) -> bool:
        """Whether the fold runs against the round's global weights (a
        delta or a group needs them; a dense frame is whole weights)."""
        return self.spec is not None or self.schema is not None

    @property
    def codec(self) -> str:
        """The ``RoundMessageLog.codec`` of the run's rounds."""
        if self.schema is not None:
            return "structured" if self.spec is None else f"structured:{self.spec.codec}"
        return "none" if self.spec is None else self.spec.codec

    def resolve(self, params: Any) -> Any:
        """The schema bound to ``params``' structure (cached by plan
        signature; ``None`` without a schema)."""
        from repro.federated.agg_engine import plan_for

        if self.schema is None:
            return None
        if self._resolved is None or self._resolved.plan.signature != plan_for(params).signature:
            self._resolved = self.schema.resolve(params)
        return self._resolved

    def encoder(self, client: Any = None) -> Any:
        """A fresh per-client encoder holding that client's error-feedback
        residual (``None`` for dense frames).  A compressed form prefers
        the client's own ``compressor`` (``FLClient(compression=...)``),
        so its residual survives worker restarts over the same client."""
        if self.schema is not None:
            return StructuredCompressor(self.schema, self.spec)
        if self.spec is None:
            return None
        owned = getattr(client, "compressor", None)
        return owned if owned is not None else ClientCompressor(self.spec)

    def encode(
        self, encoder: Any, global_params: Any, local_params: Any, round_idx: int
    ) -> Any:
        """The update as the server folds it: a delta or groups tagged
        with ``round_idx``, the base they were taken against (dense: the
        local weights themselves)."""
        if encoder is None:
            return local_params
        return encoder.encode(global_params, local_params, base_round=round_idx)

    def reply(
        self, encoder: Any, global_params: Any, local_params: Any, round_idx: int
    ) -> Tuple[Dict[str, Any], bytes]:
        """A worker's ``c_msg_train``: the header fields this form adds
        (the sender's byte accounting) and the serialized body."""
        from repro.federated.agg_engine import plan_for

        update = self.encode(encoder, global_params, local_params, round_idx)
        if self.schema is not None:
            # Dense equivalent = the FULL model's fp32 bytes: the saving
            # reported is "groups instead of the whole pytree".
            return {
                "structured": 1,
                "dense_bytes": int(plan_for(global_params).total_elems * 4),
                "group_bytes": {str(k): int(v) for k, v in update.group_wire_bytes().items()},
                "group_dense": {str(k): int(v) for k, v in update.group_dense_bytes().items()},
            }, serialize_structured(update)
        if self.spec is not None:
            return {
                "codec": update.codec, "dense_bytes": int(update.dense_bytes),
            }, serialize_update(update)
        return {}, serialize_pytree(local_params)

    def decode(self, payload: BytesLike, template: Any) -> Any:
        """The driver's decode of a ``c_msg_train`` body (dense frames
        into ``template``'s structure).  Raises
        :class:`~repro.checkpoint.serializer.DeserializationError` on a
        corrupt frame in every form."""
        if self.schema is not None:
            return deserialize_structured(payload)
        if self.spec is not None:
            return deserialize_update(payload)
        return deserialize_pytree(payload, template)

    def park(self, base: Any, update: Any) -> Any:
        """What a late update parks as: base-independent weights (a
        delta, per group, materialized while its origin base is on hand;
        a dense update as it is)."""
        if self.schema is not None:
            return materialize_structured(base, update, self.resolve(base))
        if self.spec is not None:
            return materialize_update(base, update)
        return update

    def message_sizes(self, params: Any, dense_frame_bytes: int) -> Dict[str, Any]:
        """The ``c_msg_train`` fields of a
        :class:`~repro.federated.messages.RoundMessageLog` for a model
        shaped like ``params``, whose dense frame is ``dense_frame_bytes``
        long.  Compressed and structured frames are fixed-width given the
        element counts, so the sizes are exact; each is tagged as every
        round's reply is."""
        from repro.federated.agg_engine import plan_for

        sizes: Dict[str, Any] = {"codec": self.codec, "c_msg_train_bytes": dense_frame_bytes}
        if not self.against_base:
            return sizes
        sizes["c_msg_train_dense_bytes"] = plan_for(params).total_elems * 4
        if self.schema is None:
            assert self.spec is not None
            sizes["c_msg_train_bytes"] = compressed_wire_bytes(
                plan_for(params).total_elems, self.spec, base_round=0
            )
            return sizes
        update = self.encode(self.encoder(), params, params, 0)
        sizes["c_msg_train_bytes"] = len(serialize_structured(update))
        sizes["group_wire_bytes"] = update.group_wire_bytes()
        sizes["group_dense_bytes"] = update.group_dense_bytes()
        return sizes
