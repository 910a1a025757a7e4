"""``FLClient``'s compiled step donates the weights and optimizer state it
produced itself, and nothing a caller still holds: the received weights
stay whole, the state of every step after the first is consumed, a
wrapper that hands its state back loses nothing, every batch shape's
programs compile on the first call that sees it, and the device's bytes
in use are counted only where the backend reports them."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.federated import FLClient
from repro.federated.compression import ClientCompressor, parse_compression
from repro.optim import make_optimizer


class _Silo:
    client_id = "c0"

    def __init__(self, n=23, seed=0):
        rng = np.random.default_rng(seed)
        self.x = rng.standard_normal((n, 6)).astype(np.float32)
        self.y = rng.standard_normal((n, 3)).astype(np.float32)

    def batches(self, batch, split="train"):
        for i in range(0, len(self.y), batch):
            yield self.x[i:i + batch], self.y[i:i + batch]


def _loss(p, batch):
    x, y = batch
    return jnp.mean((jnp.tanh(x @ p["w1"]) @ p["w2"] - y) ** 2)


def _params():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    return {"w1": jax.random.normal(k1, (6, 8)), "w2": jax.random.normal(k2, (8, 3))}


def _client(optimizer=None, epochs=2, loss=_loss, silo=None):
    return FLClient("c0", silo or _Silo(), loss, optimizer or make_optimizer("adamw", 1e-2),
                    batch_size=5, local_epochs=epochs,
                    eval_fn=lambda p, b: {"loss_sum": loss(p, b) * b[0].shape[0]})


def _watch(client):
    """Record the (params, opt_state) each step call gets."""
    seen = []
    step = client._train_step

    def watched(params, opt_state, batch):
        seen.append((params, opt_state))
        return step(params, opt_state, batch)
    client._train_step = watched
    return seen


def _deleted(tree):
    return [leaf.is_deleted() for leaf in jax.tree.leaves(tree)]


def test_received_weights_stay_whole():
    client = _client()
    received = _params()
    before = jax.tree.map(np.asarray, received)
    result = client.train(received)
    assert not any(_deleted(received))
    jax.tree.map(np.testing.assert_array_equal, jax.tree.map(np.asarray, received), before)
    assert np.isfinite(client.evaluate(received).metrics["loss"])
    update = ClientCompressor(parse_compression("int8")).encode(received, result.params)
    assert update.codec == "int8"
    assert not any(_deleted(result.params))


@pytest.mark.parametrize("optimizer", [make_optimizer("adamw", 1e-2),
                                       make_optimizer("sgdm", 0.1, momentum=0.0)])
def test_every_step_after_the_first_consumes_its_inputs(optimizer):
    client = _client(optimizer)
    seen = _watch(client)
    received = _params()
    client.train(received)
    assert len(seen) == 10                      # 2 epochs of 5 batches (the last short)
    first_params, first_state = seen[0]
    assert first_params is received and not any(_deleted(first_state))
    for params, state in seen[1:]:
        assert all(_deleted(params)) and all(_deleted(state))


def test_a_wrapper_that_hands_its_state_back_loses_nothing():
    """A step hook that skips the optimizer hands back the state it was
    given; the step never donates a state it did not return, so the next
    call still finds it, and the weights still move."""
    client = _client(make_optimizer("sgdm", 0.1, momentum=0.0))
    step = client._train_step

    def frozen(params, opt_state, batch):
        params, _, loss = step(params, opt_state, batch)
        return params, opt_state, loss
    client._train_step = frozen
    received = _params()
    result = client.train(received)
    moved = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), result.params, received)
    assert all(v > 0 for v in jax.tree.leaves(moved))


def test_the_round_matches_a_step_without_donation():
    client = _client()
    received = _params()
    got = client.train(received).params
    opt = client.optimizer
    step = jax.jit(lambda p, s, b: (lambda l_g: opt.update(l_g[1], s, p))(
        jax.value_and_grad(_loss)(p, b)))
    p, s = received, opt.init(received)
    for _ in range(2):
        for b in _Silo().batches(5):
            p, s = step(p, s, b)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7), got, p)


def _count_compiles():
    counter = [0]

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            counter[0] += 1
    jax.monitoring.register_event_duration_secs_listener(listen)
    return counter


def test_later_rounds_and_clients_compile_nothing():
    """The first round compiles both programs of each batch shape, the
    full batch and the short last one; a second round, and a second
    client of the same loss and optimizer, compile nothing."""
    loss = lambda p, b: _loss(p, b) * 1.0   # a loss no other test has compiled
    opt = make_optimizer("sgdm", 0.05, momentum=0.5)
    client = _client(opt, loss=loss)
    received = _params()
    client.train(received)
    compiles = _count_compiles()
    client.train(received)
    _client(opt, loss=loss, silo=_Silo(seed=1)).train(received)
    assert compiles[0] == 0


@dataclasses.dataclass
class _Device:
    stats: dict

    def memory_stats(self):
        return self.stats


@pytest.mark.parametrize("stats", [None, {"bytes_in_use": 12345, "peak_bytes_in_use": 99999}])
def test_hbm_in_use_is_counted_where_the_backend_reports_memory(monkeypatch, stats):
    from repro.federated import client as client_mod

    monkeypatch.setattr(client_mod, "_device_of", lambda tree: _Device(stats))
    log = spans.SpanLog("c0")
    with spans.collect(log):
        _client().train(_params())
    if stats is None:
        assert "hbm_in_use_bytes" not in log.counters
    else:
        assert log.counters["hbm_in_use_bytes"] == 12345
    assert log.counters["step_calls"] == 10

