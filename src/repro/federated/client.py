"""FL client: local training over a private silo (paper §3).

Each client receives the global weights, runs `local_epochs` of SGD/AdamW
over its silo, and returns (updated weights, n_samples, wall time). The
evaluation phase runs the silo's test split and returns scalar metrics.

The train step is compiled once per (loss, optimizer) pair and batch
shape, shared by every client that trains with that pair, and reused
across rounds — like a real client process would.  From a round's second
step on, the step donates the weights and optimizer state it is handed
back, so that a silo holds one copy of each on the device while its
steps run: the new values are written over the old.

With wire compression enabled the client also owns its error-feedback
residual (:class:`~repro.federated.compression.ClientCompressor`): the
part of each update a codec dropped is carried into the next round's
delta, client-side, which is what keeps sparsified training convergent.
The buffer belongs to the *client* — a restarted worker thread reusing
the same client object keeps its residual; a replacement VM (fresh
process) starts from zero, costing only a little extra compression
error on its next update.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans


@dataclasses.dataclass
class ClientResult:
    client_id: str
    params: Any
    n_samples: int
    train_time_s: float


@dataclasses.dataclass
class EvalResult:
    client_id: str
    metrics: Dict[str, float]
    n_samples: int
    eval_time_s: float


class FLClient:
    """One cross-silo FL client.

    loss_fn(params, batch) -> scalar; batch is whatever the silo yields
    (tuple converted via `batch_fn`). eval_fn(params, batch) -> dict of
    per-batch values reduced over batches: keys with a ``_sum`` suffix
    (e.g. ``{"nll_sum": ...}``) are example-weighted sums that `evaluate`
    averages (dividing by the split size, suffix stripped); any other key
    is reported as its plain total across batches, untouched.
    """

    def __init__(
        self,
        client_id: str,
        silo: Any,
        loss_fn: Callable[[Any, Any], jnp.ndarray],
        optimizer: Any,
        batch_size: int = 32,
        local_epochs: int = 1,
        batch_fn: Optional[Callable] = None,
        eval_fn: Optional[Callable[[Any, Any], Dict[str, jnp.ndarray]]] = None,
        compression: Any = None,
    ) -> None:
        self.client_id = client_id
        self.silo = silo
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.batch_size = batch_size
        self.local_epochs = local_epochs
        self.batch_fn = batch_fn or (lambda b: b)
        self.eval_fn = eval_fn
        self._opt_state = None
        # Client-owned compression state: the error-feedback residual
        # stays with the silo (not the transport invocation), so worker
        # restarts over the same client object keep it.  The transport
        # worker and AsyncFLServer both prefer this compressor when the
        # wire path is compressed.
        self.compressor = None
        if compression is not None:
            from .compression import ClientCompressor, parse_compression

            spec = parse_compression(compression)
            if spec is not None:
                self.compressor = ClientCompressor(spec)

        # The step's last (weights, optimizer state), which the next call
        # may donate; batch signatures whose programs are compiled.
        self._own: Optional[Tuple[Any, Any]] = None
        self._compiled: Set[Any] = set()
        self._train_step = self._step
        self._jit_eval = jax.jit(eval_fn) if eval_fn is not None else None

    def _step(self, params: Any, opt_state: Any, batch: Any) -> Tuple[Any, Any, Any]:
        """One local step.  It donates ``params`` and ``opt_state`` only
        where both are what its previous call returned: never the
        received weights or ``optimizer.init``'s state, which a caller
        may still hold, nor a state that a wrapper around this step
        handed back in place of the one returned.  Both programs are
        compiled at a batch signature's first call, so a round's later
        steps compile nothing."""
        args = (self.loss_fn, self.optimizer, params, opt_state, batch)
        leaves, treedef = jax.tree.flatten(batch)
        sig = (treedef, tuple((np.shape(x), np.result_type(x)) for x in leaves))
        if sig not in self._compiled:
            _STEP.lower(*args).compile()
            _DONATING_STEP.lower(*args).compile()
            self._compiled.add(sig)
        own = self._own is not None and params is self._own[0] and opt_state is self._own[1]
        out = (_DONATING_STEP if own else _STEP)(*args)
        self._own = out[:2]
        return out

    # -- training phase ------------------------------------------------------
    def train(self, global_params: Any) -> ClientResult:
        """Local training: span ``fl.train``, with counters ``step_calls``
        and ``step_dispatch_s`` (host time inside the step calls), the
        closing ``block_until_ready`` as span ``fl.drain``, and after it
        counter ``hbm_in_use_bytes`` (the device's bytes in use, where
        the backend reports memory)."""
        with spans.span("fl.train"):
            t0 = time.monotonic()
            params = global_params
            # Fresh optimizer state per round (clients are stateless across
            # rounds w.r.t. the optimizer; only weights flow through the server).
            opt_state = self.optimizer.init(params)
            # n_samples is the silo's per-epoch example count — the FedAvg
            # weight (§3).  Count one epoch's pass exactly rather than
            # dividing the multi-epoch total: with ragged last batches the
            # per-epoch counts are equal, but integer-dividing the sum would
            # under-count whenever an epoch's total isn't a multiple of
            # local_epochs, skewing weights across silos with different
            # batch remainders.
            n_first_epoch = 0
            last_loss = None
            calls = 0
            dispatch_s = 0.0
            try:
                for epoch in range(self.local_epochs):
                    for raw in self.silo.batches(self.batch_size, split="train"):
                        batch = self.batch_fn(raw)
                        t_call = time.perf_counter()
                        params, opt_state, last_loss = self._train_step(params, opt_state, batch)
                        dispatch_s += time.perf_counter() - t_call
                        calls += 1
                        if epoch == 0:
                            n_first_epoch += _batch_count(raw)
            finally:
                self._own = None     # the result is the caller's: hold and donate none of it
            spans.add("step_calls", calls)
            spans.add("step_dispatch_s", dispatch_s)
            with spans.span("fl.drain"):
                jax.block_until_ready(last_loss)
            stats = _device_of(params).memory_stats()
            if stats is not None:
                spans.add("hbm_in_use_bytes", stats["bytes_in_use"])
            return ClientResult(
                client_id=self.client_id,
                params=params,
                n_samples=n_first_epoch,
                train_time_s=time.monotonic() - t0,
            )

    def encode_update(self, global_params: Any, local_params: Any) -> Any:
        """Compress this round's update with the client-owned
        error-feedback buffer (requires ``compression=`` at init)."""
        if self.compressor is None:
            raise ValueError(
                f"client {self.client_id!r} has no compressor; pass "
                "compression= when constructing the FLClient"
            )
        return self.compressor.encode(global_params, local_params)

    # -- evaluation phase -----------------------------------------------------
    def evaluate(self, aggregated_params: Any) -> EvalResult:
        """The evaluation phase on the silo's test split (span ``fl.evaluate``)."""
        with spans.span("fl.evaluate"):
            t0 = time.monotonic()
            sums: Dict[str, float] = {}
            n = 0
            for raw in self.silo.batches(self.batch_size, split="test"):
                batch = self.batch_fn(raw)
                if self._jit_eval is not None:
                    out = self._jit_eval(aggregated_params, batch)
                else:
                    out = {"loss_sum": self.loss_fn(aggregated_params, batch) * _batch_count(raw)}
                for k, v in out.items():
                    sums[k] = sums.get(k, 0.0) + float(v)
                n += _batch_count(raw)
            # Average only the keys that declare themselves example-weighted
            # sums via a "_sum" suffix, stripping exactly that suffix.  A
            # blanket k.replace("_sum", "")/n would mangle keys merely
            # *containing* the substring (loss_summary -> losmary) and turn
            # already-normalized metrics into nonsense rates.
            metrics = {
                (k[: -len("_sum")] if k.endswith("_sum") else k):
                    (v / max(n, 1) if k.endswith("_sum") else v)
                for k, v in sums.items()
            }
            return EvalResult(
                client_id=self.client_id,
                metrics=metrics,
                n_samples=n,
                eval_time_s=time.monotonic() - t0,
            )


def train_step(loss_fn: Callable, optimizer: Any, params: Any, opt_state: Any,
               batch: Any) -> Tuple[Any, Any, Any]:
    """One optimizer step on one batch: (new params, new state, loss)."""
    loss, grads = jax.value_and_grad(loss_fn)(params, batch)
    params, opt_state = optimizer.update(grads, opt_state, params)
    return params, opt_state, loss


_STEP = jax.jit(train_step, static_argnums=(0, 1))
_DONATING_STEP = jax.jit(train_step, static_argnums=(0, 1), donate_argnums=(2, 3))


def _device_of(tree: Any) -> Any:
    """The device of the tree's first device array, else the default one."""
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array):
            return next(iter(leaf.devices()))
    return jax.devices()[0]


def _batch_count(raw) -> int:
    if isinstance(raw, tuple):
        return int(np.shape(raw[0])[0])
    if isinstance(raw, dict):
        return int(np.shape(next(iter(raw.values())))[0])
    return int(np.shape(raw)[0])
