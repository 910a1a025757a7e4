"""Compressed wire path: codec roundtrips, the fused dequantize-and-fold
property (quantize -> fused fold == dense fp32 fold of the decompressed
updates, within codec tolerance, across ragged pytrees), error-feedback
convergence, wire framing (truncation raises the typed error), builder
validation, byte accounting, sim-vs-live parity with compression on, and
the chaos corrupt_frame interaction on a compressed frame."""
import numpy as np
import pytest

import jax.numpy as jnp

try:  # hypothesis is an optional dev dependency (requirements-dev.txt)
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # property tests skip cleanly without it
    from _hypothesis_stub import given, settings, st

from conftest import assert_trees_close, ragged_trees
from repro.checkpoint.serializer import DeserializationError
from repro.core import Experiment
from repro.federated import (
    AsyncFLServer,
    ClientCompressor,
    CompressedUpdate,
    CompressionSpec,
    DeterministicSchedule,
    FaultPlan,
    FLClient,
    LiveRoundDriver,
    compress,
    compressed_wire_bytes,
    decompress,
    deserialize_update,
    parse_compression,
    plan_for,
    serialize_update,
)
from repro.federated.agg_engine import AggregationEngine
from repro.federated.aggregation import fedavg
from repro.federated.chaos import FaultSpec, verify_fault_pairing
from repro.federated.compression import QBLOCK, topk_count
from repro.kernels.fedavg_reduce import BLOCK, dequant_fold
from test_transport import (
    assert_params_close,
    init_params,
    make_paced_clients,
    trace_signature,
)

CODEC_SPECS = [
    CompressionSpec("int8"),
    CompressionSpec("fp16"),
    CompressionSpec("topk", k_frac=0.1),
]


def _rand_vec(n, seed, scale=0.1):
    return (np.random.default_rng(seed).standard_normal(n) * scale).astype(
        np.float32
    )


# ---------------------------------------------------------------------------
# Codecs: roundtrip + tolerance + wire sizes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", CODEC_SPECS, ids=lambda s: s.codec)
def test_codec_wire_roundtrip_is_exact(spec):
    """serialize -> deserialize reproduces the codec output bit-exactly."""
    vec = _rand_vec(3 * QBLOCK + 17, seed=0)
    cu = compress(vec, spec)
    back = deserialize_update(serialize_update(cu))
    assert back.codec == cu.codec
    assert back.total_elems == cu.total_elems
    np.testing.assert_array_equal(np.asarray(back.data), np.asarray(cu.data))
    if cu.scales is not None:
        np.testing.assert_array_equal(back.scales, cu.scales)
    if cu.indices is not None:
        np.testing.assert_array_equal(back.indices, cu.indices)
    np.testing.assert_array_equal(decompress(back), decompress(cu))


def test_int8_error_bounded_by_half_scale_per_block():
    vec = _rand_vec(2 * QBLOCK + 100, seed=1)
    cu = compress(vec, CompressionSpec("int8"))
    err = np.abs(decompress(cu) - vec)
    # Per block: |x - q*scale| <= scale/2 (round-to-nearest).
    for b in range(cu.scales.size):
        lo, hi = b * QBLOCK, min((b + 1) * QBLOCK, vec.size)
        assert err[lo:hi].max() <= cu.scales[b] / 2 + 1e-7


def test_topk_keeps_largest_magnitudes():
    vec = _rand_vec(5000, seed=2)
    spec = CompressionSpec("topk", k_frac=0.1)
    cu = compress(vec, spec)
    k = topk_count(vec.size, 0.1)
    assert cu.indices.size == k == cu.data.size
    kept = set(cu.indices.tolist())
    cutoff = np.sort(np.abs(vec))[-k]
    # Everything strictly above the cutoff magnitude must be kept.
    for i in np.nonzero(np.abs(vec) > cutoff)[0]:
        assert int(i) in kept
    # Indices arrive sorted (the wire validator requires it).
    assert np.all(np.diff(cu.indices) > 0)


def test_zero_block_quantizes_to_zero():
    vec = np.zeros(QBLOCK + 5, np.float32)
    vec[-1] = 0.25  # second block non-zero, first block all-zero
    cu = compress(vec, CompressionSpec("int8"))
    assert cu.scales[0] == 0.0
    np.testing.assert_array_equal(decompress(cu)[:QBLOCK], 0.0)
    assert decompress(cu)[-1] == pytest.approx(0.25, rel=0.01)


@pytest.mark.parametrize("spec", CODEC_SPECS, ids=lambda s: s.codec)
def test_wire_bytes_beat_dense_and_match_predictor(spec):
    n = 4 * QBLOCK
    cu = compress(_rand_vec(n, seed=3), spec)
    assert cu.dense_bytes == 4 * n
    assert cu.wire_bytes < cu.dense_bytes
    # Frame sizes are data-independent given n: the accounting predictor
    # must match the real serialized size exactly.
    assert compressed_wire_bytes(n, spec) == cu.wire_bytes
    floor = {"int8": 3.5, "fp16": 1.9, "topk": 5.0}[spec.codec]
    assert cu.dense_bytes / cu.wire_bytes > floor


# ---------------------------------------------------------------------------
# Wire framing: corruption always raises the typed error
# ---------------------------------------------------------------------------

def test_truncated_or_garbled_frame_raises_typed_error():
    frame = serialize_update(compress(_rand_vec(QBLOCK, seed=4),
                                      CompressionSpec("int8")))
    for bad in (
        frame[: len(frame) // 2],  # ChaosClient.mangle_payload's cut
        frame[:-3],
        b"not msgpack at all",
        b"",
    ):
        with pytest.raises(DeserializationError):
            deserialize_update(bad)


def test_internally_inconsistent_frames_raise():
    import msgpack

    ok = {"v": 1, "codec": "int8", "n": 8, "data": b"\x01" * 8,
          "scales": np.ones(1, np.float32).tobytes()}
    bad_frames = [
        {**ok, "v": 2},
        {**ok, "codec": "lz4"},
        {**ok, "n": 0},
        {**ok, "data": b"\x01" * 7},       # length mismatch
        {**ok, "scales": b"\x00" * 3},     # not a whole float32
        {"v": 1, "codec": "topk", "n": 8, "data": b"\x01" * 4,
         "idx": np.array([3, 1], np.int32).tobytes()},  # unsorted
        {"v": 1, "codec": "topk", "n": 8, "data": b"\x01" * 4,
         "idx": np.array([1, 9], np.int32).tobytes()},  # out of range
    ]
    for obj in bad_frames:
        with pytest.raises(DeserializationError):
            deserialize_update(msgpack.packb(obj, use_bin_type=True))


# ---------------------------------------------------------------------------
# parse_compression / spec validation
# ---------------------------------------------------------------------------

def test_parse_compression_accepts_all_forms():
    assert parse_compression(None) is None
    assert parse_compression("int8") == CompressionSpec("int8")
    assert parse_compression("fp16").codec == "fp16"
    assert parse_compression("topk").k_frac == 0.1
    assert parse_compression("topk:0.05").k_frac == 0.05
    spec = CompressionSpec("topk", k_frac=0.25)
    assert parse_compression(spec) is spec


def test_parse_compression_rejects_bad_knobs():
    with pytest.raises(ValueError, match="codec"):
        parse_compression("lz4")
    with pytest.raises(ValueError, match="k_frac"):
        parse_compression("topk:1.5")
    with pytest.raises(ValueError, match="k_frac"):
        CompressionSpec("topk", k_frac=0.0)
    with pytest.raises(ValueError, match="topk"):
        parse_compression("int8:0.5")
    with pytest.raises(ValueError):
        parse_compression(123)


# ---------------------------------------------------------------------------
# Fused dequantize-and-fold kernel (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("codec", ["int8", "fp16"])
def test_dequant_fold_kernel_matches_reference(codec):
    n = 2 * BLOCK + 123
    lp = 3 * BLOCK
    vec = _rand_vec(n, seed=5)
    cu = compress(vec, CompressionSpec(codec))
    data = np.zeros(lp, dtype=np.asarray(cu.data).dtype)
    data[:n] = cu.data
    scales = (
        np.asarray(cu.scales, np.float32)
        if cu.scales is not None else np.ones(lp // BLOCK, np.float32)
    )
    acc0 = _rand_vec(lp, seed=6)
    out = dequant_fold(
        jnp.asarray(acc0), jnp.asarray(data), jnp.asarray(scales),
        jnp.float32(2.5), interpret=True,
    )
    ref = acc0.copy()
    ref[:n] += 2.5 * decompress(cu)
    np.testing.assert_allclose(np.asarray(out)[:n], ref[:n], atol=1e-5)
    # Padding tail stays untouched by the fold (quantized pad is zero).
    np.testing.assert_allclose(np.asarray(out)[n:], ref[n:], atol=1e-6)


@pytest.mark.parametrize("codec", ["int8", "fp16"])
def test_dequant_fold_partial_last_row_block_matches_jnp_fold(codec):
    """The kernel walks ROWS quantization blocks per grid step; a block
    count that is not a multiple of ROWS leaves a partial last step,
    which must fold exactly like the jitted jnp fold — and no row past
    the accumulator may be written."""
    from repro.federated.agg_engine import _flat_dequant_fold_jnp
    from repro.kernels.fedavg_reduce import ROWS

    nb = 2 * ROWS + 5
    lp = nb * BLOCK
    cu = compress(_rand_vec(lp - 77, seed=8), CompressionSpec(codec))
    data = np.zeros(lp, dtype=np.asarray(cu.data).dtype)
    data[: cu.total_elems] = cu.data
    scales = (
        np.asarray(cu.scales, np.float32)
        if cu.scales is not None else np.ones(nb, np.float32)
    )
    acc0 = _rand_vec(lp, seed=9)
    args = (jnp.asarray(data), jnp.asarray(scales), jnp.float32(0.75))
    out = dequant_fold(jnp.asarray(acc0), *args, interpret=True)
    want = _flat_dequant_fold_jnp(jnp.asarray(acc0), *args)
    assert out.shape == (lp,)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_dequant_fold_decodes_every_fp16_bit_pattern():
    """fp16 payloads enter the kernel as uint16 bits (Mosaic cannot load
    float16 vectors); the in-kernel decode must equal astype(float32) on
    all 65536 patterns — subnormals, infinities and NaNs included."""
    from repro.kernels.fedavg_reduce import _half_bits_to_f32

    bits = np.arange(1 << 16, dtype=np.uint16)
    want = bits.view(np.float16).astype(np.float32)
    got = np.asarray(_half_bits_to_f32(jnp.asarray(bits)))
    nan = np.isnan(want)
    np.testing.assert_array_equal(np.isnan(got), nan)
    np.testing.assert_array_equal(got[~nan].view(np.uint32), want[~nan].view(np.uint32))


def test_dequant_fold_rejects_unpadded_acc():
    with pytest.raises(ValueError, match="BLOCK"):
        dequant_fold(
            jnp.zeros(BLOCK + 1, jnp.float32),
            jnp.zeros(BLOCK + 1, jnp.int8),
            jnp.ones(1, jnp.float32),
            jnp.float32(1.0),
            interpret=True,
        )


# ---------------------------------------------------------------------------
# Property: quantize -> fused fold == dense fp32 fold (per codec,
# ragged pytrees)
# ---------------------------------------------------------------------------

def _fused_vs_dense_fold(codec, n_clients, seed, use_pallas):
    """The tentpole property, shared by the hypothesis + smoke tests."""
    spec = (
        CompressionSpec(codec) if codec != "topk"
        else CompressionSpec("topk", k_frac=0.3)
    )
    trees, weights = ragged_trees(n_clients, seed=seed)
    base, _ = ragged_trees(1, seed=seed + 1000)
    base = base[0]
    plan = plan_for(base)
    base_flat = np.asarray(plan.flatten(base))

    engine = AggregationEngine(
        use_pallas=use_pallas, interpret=True if use_pallas else None
    )
    agg = engine.streaming(base=base)
    updates = []
    for t, w in zip(trees, weights):
        cu = compress(np.asarray(plan.flatten(t)) - base_flat, spec)
        updates.append((cu, w))
        agg.add(cu, w)  # routes to add_compressed
    fused = agg.result()

    # Dense fp32 oracle over the *decompressed* updates: the fused path
    # must match it to float32 accuracy (no codec tolerance needed —
    # both sides see identical quantized values).
    wsum = float(sum(w for _, w in updates))
    acc = np.zeros(plan.total_elems, np.float64)
    for cu, w in zip((u for u, _ in updates), (w for _, w in updates)):
        acc += np.float64(w) * decompress(cu)
    dense_vec = base_flat + (acc / wsum).astype(np.float32)
    dense = plan.unflatten(jnp.asarray(dense_vec, jnp.float32))
    assert_trees_close(fused, dense)

    # And the codec-tolerance bound vs the *uncompressed* average: the
    # weighted mean of per-update errors never exceeds the worst one.
    raw = fedavg(trees, weights)
    per_update_err = max(
        float(np.abs(
            decompress(cu) - (np.asarray(plan.flatten(t)) - base_flat)
        ).max())
        for (cu, _), t in zip(updates, trees)
    )
    tol = per_update_err + 1e-4
    got_flat = np.asarray(plan.flatten(fused))
    want_flat = np.asarray(plan.flatten(raw))
    assert float(np.abs(got_flat - want_flat).max()) <= tol


@pytest.mark.parametrize("codec", ["int8", "fp16", "topk"])
def test_fused_fold_matches_dense_fold(codec):
    _fused_vs_dense_fold(codec, n_clients=3, seed=0, use_pallas=False)


@pytest.mark.parametrize("codec", ["int8", "fp16"])
def test_fused_fold_matches_dense_fold_pallas(codec):
    _fused_vs_dense_fold(codec, n_clients=3, seed=1, use_pallas=True)


@settings(max_examples=15, deadline=None)
@given(
    codec=st.sampled_from(["int8", "fp16", "topk"]),
    n_clients=st.integers(min_value=1, max_value=5),
    seed=st.integers(min_value=0, max_value=50),
)
def test_fused_fold_matches_dense_fold_property(codec, n_clients, seed):
    _fused_vs_dense_fold(codec, n_clients, seed, use_pallas=False)


# ---------------------------------------------------------------------------
# Error feedback
# ---------------------------------------------------------------------------

def test_error_feedback_carries_dropped_mass():
    """What top-k drops this round is in the next round's encode input."""
    spec = CompressionSpec("topk", k_frac=0.5, error_feedback=True)
    comp = ClientCompressor(spec)
    base = {"w": jnp.zeros((6,), jnp.float32)}
    local = {"w": jnp.asarray([1.0, -2.0, 0.1, 0.2, 3.0, -0.3], jnp.float32)}
    cu1 = comp.encode(base, local)
    # k=3 keeps {-2, 1, 3}; residual holds the dropped {0.1, 0.2, -0.3}.
    resid = comp._residual
    np.testing.assert_allclose(
        np.sort(np.abs(resid[np.abs(resid) > 0])), [0.1, 0.2, 0.3],
        atol=1e-6,
    )
    # Second round with a zero delta: the residual alone drives the
    # update, so the dropped coordinates ship now.
    cu2 = comp.encode(base, base)
    shipped = decompress(cu2)
    np.testing.assert_allclose(
        np.sort(np.abs(shipped[np.abs(shipped) > 0])), [0.1, 0.2, 0.3],
        atol=1e-3,  # fp16 value storage
    )


def test_error_feedback_off_keeps_no_state():
    spec = CompressionSpec("topk", k_frac=0.5, error_feedback=False)
    comp = ClientCompressor(spec)
    base = {"w": jnp.zeros((6,), jnp.float32)}
    local = {"w": jnp.asarray([1.0, -2.0, 0.1, 0.2, 3.0, -0.3], jnp.float32)}
    comp.encode(base, local)
    assert comp._residual is None
    cu2 = comp.encode(base, base)
    assert float(np.abs(decompress(cu2)).max()) == 0.0


# ---------------------------------------------------------------------------
# The device encoder against the numpy formula it replaced
# ---------------------------------------------------------------------------

def _numpy_encode(x, codec):
    """The host quantizer the device encoder replaced, as the oracle:
    codes of the ``n`` elements, scales, and the residual of the padded
    blocks ``x - decompress``."""
    n = x.size
    nb = -(-n // QBLOCK)
    padded = np.zeros(nb * QBLOCK, np.float32)
    padded[:n] = x
    blocks = padded.reshape(nb, QBLOCK)
    if codec == "fp16":
        data = blocks.astype(np.float16)
        return data.reshape(-1)[:n], None, blocks - data.astype(np.float32)
    absmax = np.max(np.abs(blocks), axis=1)
    scales = (absmax / 127.0).astype(np.float32)
    safe = np.where(scales > 0.0, scales, np.float32(1.0))
    q = np.clip(np.rint(blocks / safe[:, None]), -127, 127).astype(np.int8)
    q[scales == 0.0] = 0
    deq = q.astype(np.float32) * scales[:, None]
    return q.reshape(-1)[:n], scales, blocks - deq


def _seeded_round(n, seed, zero_block=False):
    """Global and local weights (two leaves) and a residual of padded
    blocks, zero past ``n``."""
    rng = np.random.default_rng(seed)
    g = (rng.standard_normal(n) * 0.1).astype(np.float32)
    local = (g + rng.standard_normal(n) * 0.01).astype(np.float32)
    nb = -(-n // QBLOCK)
    resid = np.zeros(nb * QBLOCK, np.float32)
    resid[:n] = rng.standard_normal(n) * 1e-3
    if zero_block:
        local[QBLOCK:2 * QBLOCK] = g[QBLOCK:2 * QBLOCK]
        resid[QBLOCK:2 * QBLOCK] = 0.0
    cut = n // 3
    tree = lambda v: {"a": jnp.asarray(v[:cut]), "b": jnp.asarray(v[cut:])}
    return tree(g), tree(local), resid.reshape(nb, QBLOCK), local - g


ENCODER_CASES = {
    "n_below_one_block": (QBLOCK - 1000, False),
    "partial_last_block": (QBLOCK + 17, False),
    "all_zero_block": (3 * QBLOCK + 5, True),
    "several_blocks": (6 * QBLOCK, False),
}


@pytest.mark.parametrize("codec", ["int8", "fp16"])
@pytest.mark.parametrize("case", sorted(ENCODER_CASES))
def test_device_encoder_matches_numpy_oracle(case, codec):
    """Codes and scales equal the host formula's, bar one code at a
    rounding tie in at most 1e-5 of the elements; the residual within
    one ulp of the encoded value (the device may fuse ``x - q * s``
    into one multiply-add, which rounds once where numpy rounds twice)."""
    from repro.federated.compression import _encode_update

    n, zero_block = ENCODER_CASES[case]
    g, local, resid, delta = _seeded_round(n, seed=len(case), zero_block=zero_block)
    x = resid.copy()
    x.reshape(-1)[:n] += delta
    want_data, want_scales, want_resid = _numpy_encode(x.reshape(-1)[:n], codec)

    data, scales, new_resid = _encode_update(g, local, jnp.asarray(resid), codec=codec)
    data, new_resid = np.asarray(data), np.asarray(new_resid)
    assert data.dtype == want_data.dtype and data.shape == (n,)
    if codec == "int8":
        np.testing.assert_array_equal(np.asarray(scales), want_scales)
        off = np.abs(data.astype(np.int16) - want_data.astype(np.int16))
        assert off.max() <= 1 and np.count_nonzero(off) <= 1e-5 * n
        if zero_block:
            assert want_scales[1] == 0.0
            np.testing.assert_array_equal(data[QBLOCK:2 * QBLOCK], 0)
    else:
        assert scales is None
        np.testing.assert_array_equal(data, want_data)
    assert new_resid.shape == resid.shape
    ulp = np.spacing(np.abs(x)).astype(np.float32)
    assert np.all(np.abs(new_resid - want_resid) <= ulp)
    np.testing.assert_array_equal(new_resid.reshape(-1)[n:], 0.0)


@pytest.mark.parametrize("codec", ["int8", "fp16"])
def test_device_error_feedback_ships_the_residual_next_round(codec):
    """Round 2's zero delta ships exactly what round 1's codec dropped."""
    g, local, _, delta = _seeded_round(2 * QBLOCK + 300, seed=11)
    comp = ClientCompressor(CompressionSpec(codec))
    cu1 = comp.encode(g, local)
    dropped = delta - decompress(cu1)
    np.testing.assert_allclose(
        np.asarray(comp._residual).reshape(-1)[: delta.size], dropped,
        atol=float(np.spacing(np.abs(delta).max())),
    )
    cu2 = comp.encode(g, g)
    shipped = decompress(cu2)
    # The second encode quantizes the residual alone, so it ships it to
    # within its own codec error: half a step of the block's int8 scale,
    # or of fp16 (relative 2^-11, or 2^-25 among its subnormals).
    if codec == "int8":
        np.testing.assert_allclose(shipped, dropped, rtol=0, atol=float(cu2.scales.max()) / 2)
    else:
        np.testing.assert_allclose(shipped, dropped, rtol=2.0**-11, atol=2.0**-25)
    assert np.abs(shipped).max() > 0.0


@pytest.mark.parametrize("error_feedback", [True, False])
def test_device_residual_is_a_device_array_or_none(error_feedback):
    import jax

    g, local, _, _ = _seeded_round(QBLOCK + 17, seed=12)
    comp = ClientCompressor(CompressionSpec("int8", error_feedback=error_feedback))
    for _ in range(2):
        comp.encode(g, local)
        if error_feedback:
            assert isinstance(comp._residual, jax.Array)
            assert comp._residual.shape == (2, QBLOCK)
        else:
            assert comp._residual is None
    comp.reset()
    assert comp._residual is None


def test_device_encoder_compiles_once_per_shape():
    """A fresh compressor's first encode (zero residual) and its second
    run one compiled program, counted by the jit's cache."""
    from repro.federated.compression import _encode_update

    g, local, _, _ = _seeded_round(2 * QBLOCK + 9, seed=13)
    comp = ClientCompressor(CompressionSpec("int8"))
    comp.encode(g, local)
    compiled = _encode_update._cache_size()
    comp.encode(g, local)
    ClientCompressor(CompressionSpec("int8")).encode(g, local)
    assert _encode_update._cache_size() == compiled


def test_device_encode_counts_its_copies():
    """Inside fl.encode the host receives the codes and scales alone."""
    from repro import spans

    n = 2 * QBLOCK + 9
    g, local, _, _ = _seeded_round(n, seed=14)
    comp = ClientCompressor(CompressionSpec("int8"))
    log = spans.SpanLog()
    with spans.collect(log):
        comp.encode(g, local)
    assert [s.name for s in log.spans] == ["fl.encode"]
    assert log.spans[0].nbytes == 4 * n
    assert log.counters["d2h_bytes"] == n + 4 * 3
    assert log.counters["encode_device"] == 1


def _convergence_loss(compression, n_rounds=12):
    clients = make_paced_clients(
        {"c0": 0.0, "c1": 0.0}, n_examples=(24, 24), seed=7
    )
    server = AsyncFLServer(
        clients, init_params(), schedule=DeterministicSchedule(0.0),
        compression=compression,
    )
    result = server.run(n_rounds)
    return [r.metrics["loss"] for r in result.rounds]


def test_compressed_convergence_matches_uncompressed():
    """Error feedback keeps sparsified/quantized training within epsilon
    of the uncompressed loss trajectory on the toy app."""
    raw = _convergence_loss(None)
    for codec in ("int8", "topk:0.25"):
        comp = _convergence_loss(codec)
        assert comp[-1] < raw[0]  # actually converging
        assert comp[-1] == pytest.approx(raw[-1], rel=0.15, abs=0.02)


# ---------------------------------------------------------------------------
# Builder + accounting
# ---------------------------------------------------------------------------

def test_builder_validates_compression_at_chain_time():
    exp = Experiment().aggregation(compression="topk:0.05")
    assert exp._compression == CompressionSpec("topk", k_frac=0.05)
    with pytest.raises(ValueError, match="codec"):
        Experiment().aggregation(compression="bogus")
    with pytest.raises(ValueError, match="k_frac"):
        Experiment().aggregation(compression="topk:7")


def test_builder_chains_do_not_alias_compression():
    base = Experiment()
    with_comp = base.aggregation(compression="int8")
    assert base._compression is None
    assert with_comp._compression == CompressionSpec("int8")


def test_simulator_target_rejects_compression():
    from conftest import make_toy_app, make_toy_env

    chain = (Experiment.on(make_toy_env()).app(make_toy_app())
             .aggregation(compression="int8"))
    with pytest.raises(ValueError, match="serve"):
        chain.build()


def test_round_log_accounts_wire_vs_dense():
    clients = make_paced_clients({"c0": 0.0, "c1": 0.0})
    server = AsyncFLServer(
        clients, init_params(), schedule=DeterministicSchedule(0.0),
        compression="fp16", measure_round_messages=True,
    )
    result = server.run(1)
    log = result.rounds[0].message_log
    assert log.codec == "fp16"
    assert log.c_msg_train_dense_bytes == 3 * 4  # the 3-weight toy model
    # Server->client legs stay dense.
    assert log.s_msg_train_bytes == log.s_msg_aggreg_bytes
    assert log.compression_ratio == pytest.approx(
        log.c_msg_train_dense_bytes / log.c_msg_train_bytes
    )


# ---------------------------------------------------------------------------
# Sim-vs-live parity + chaos interaction (thread transport)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("codec", ["int8", "topk:0.5"])
def test_sim_vs_live_parity_with_compression(codec):
    """Compression on both bus drivers: identical params (bit-exact —
    both drivers encode the same deterministic codecs against the same
    bases) and identical trace signatures."""
    clients = make_paced_clients({"c0": 0.0, "c1": 0.0})
    from test_transport import chain_on_arrival
    driver = (Experiment().aggregation(compression=codec)
              .transport(reply_timeout_s=30.0)
              .serve(clients, init_params()))
    chain_on_arrival(driver, *clients)
    assert isinstance(driver, LiveRoundDriver)
    assert driver.compression == parse_compression(codec)
    with driver:
        live = driver.run(2)

    server = AsyncFLServer(
        make_paced_clients({"c0": 0.0, "c1": 0.0}),
        init_params(),
        schedule=DeterministicSchedule({"c0": 0.01, "c1": 0.02}),
        compression=codec,
    )
    sim = server.run(2)

    assert_params_close(live.final_params, sim.final_params)
    assert trace_signature(driver.trace) == trace_signature(server.bus.trace)
    # The live log's c_msg_train leg measured the compressed frame.
    log = driver.message_logs[0]
    assert log.codec == parse_compression(codec).codec
    assert log.c_msg_train_dense_bytes == 12


def test_corrupt_frame_on_compressed_frame_still_recovers():
    """Chaos interaction: corrupt_frame truncates a *compressed*
    c_msg_train; decode raises the same typed DeserializationError and
    the §4.3 re-request recovery applies unchanged."""
    plan = FaultPlan([FaultSpec("corrupt_frame", "c1", 1)])
    clients = make_paced_clients({"c0": 0.0, "c1": 0.05})
    driver = (Experiment().aggregation(compression="int8").chaos(plan)
              .transport(reply_timeout_s=30.0)
              .serve(clients, init_params()))
    with driver:
        live = driver.run(2)
    from repro.core.events import UpdateArrived
    arrivals = [e for e in driver.trace
                if isinstance(e, UpdateArrived) and e.task == "c1"
                and e.round_idx == 1]
    assert [e.attempt for e in arrivals] == [2]
    pairing = verify_fault_pairing(plan, driver.trace)
    assert pairing[("corrupt_frame", "c1", 1, "train")] == "recovered"
    assert len(live.rounds) == 2
    assert np.isfinite(np.asarray(live.final_params["w"])).all()


def test_base_round_tag_survives_wire_roundtrip():
    """PR 8: the optional base-round tag rides the msgpack frame ("br")
    and deserializes back; untagged frames stay untagged (legacy)."""
    import numpy as np

    from repro.federated.compression import (
        CompressionSpec,
        compress,
        deserialize_update,
        serialize_update,
    )

    delta = np.linspace(-1, 1, 64).astype(np.float32)
    for codec in ("int8", "fp16", "topk"):
        tagged = compress(delta, CompressionSpec(codec), base_round=7)
        assert tagged.base_round == 7
        back = deserialize_update(serialize_update(tagged))
        assert back.base_round == 7
        untagged = compress(delta, CompressionSpec(codec))
        assert untagged.base_round is None
        assert deserialize_update(serialize_update(untagged)).base_round is None


def test_bad_base_round_tag_rejected():
    import numpy as np

    from repro.federated.compression import (
        CompressionSpec,
        DeserializationError,
        compress,
        deserialize_update,
        serialize_update,
    )

    cu = compress(np.ones(16, np.float32), CompressionSpec("fp16"), base_round=2)
    frame = serialize_update(cu)
    import msgpack

    obj = msgpack.unpackb(frame, raw=False)
    obj["br"] = "seven"
    with pytest.raises(DeserializationError, match="base round"):
        deserialize_update(msgpack.packb(obj, use_bin_type=True))
