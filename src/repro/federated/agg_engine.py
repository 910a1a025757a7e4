"""Fused FedAvg aggregation engine — the server's per-round hot path.

The seed implementation (`aggregation.fedavg`, kept as the correctness
oracle) reduces N client pytrees with a per-leaf Python loop of N
multiply-adds, each dispatched op-by-op and materializing N fp32
temporaries per leaf.  At cross-silo model sizes this is a pure
memory-bound streaming reduce, so the engine's job is to touch every
client byte exactly once per round.

Dispatch hierarchy (backend-aware, detected once per engine):

  TPU   — flatten-once: each client tree is raveled through a cached
          :class:`RavelPlan` (treedef / shape-layout computed once per
          model structure, reused every round — no per-round retracing
          or re-padding) into one contiguous fp32 ``(N, L)`` buffer,
          reduced by the Pallas ``fedavg_reduce`` kernel (compiled, not
          interpreted), with the stacked buffer *donated* so XLA reuses
          the HBM instead of doubling peak memory.
  CPU/GPU — one jitted fused reduce over the client trees: XLA fuses the
          weighted multiply-add chain per leaf into a single pass over
          the inputs (a dot over the client axis), with no per-round
          Python loop and no ``(N, L)`` materialization.  For buffers
          that are *already* stacked ``(N, L)`` (pod replica stacks,
          benchmarks) the reduce is a single fp32-accumulated
          ``jnp.einsum``.

A chunked mode (`reduce_flat(..., chunk_elems=...)`) streams the reduce
in O(N·block) rather than O(N·L) working memory, and
:class:`StreamingAggregator` folds clients in *as they land* (running
weighted accumulation with an O(L) donated-in-place accumulator), so
asynchronously arriving silos never require holding all N models.

Deadline-driven partial rounds (see :mod:`repro.federated.async_server`)
park updates that miss a round's ``T_round`` in a :class:`CarryOverBuffer`;
the next round's :class:`StreamingAggregator` drains it first, folding each
late silo with a staleness-discounted weight (``StreamingAggregator
.add_stale`` / ``fold_carry``), so no silo's contribution is ever dropped.

Hierarchical aggregation (see :mod:`repro.federated.hierarchy`) composes
aggregators into a tree: a regional aggregator exports its padded fp32
accumulator + weight total as a :class:`PartialSum`
(:meth:`StreamingAggregator.export_partial`) and a parent folds it with
:meth:`StreamingAggregator.fold_partial` — weighted partial sums compose
associatively, so the two-level fold is the same weighted average the
flat engine computes.
"""
from __future__ import annotations

import dataclasses
import hashlib
import time
from collections import OrderedDict
from typing import (
    Any,
    Callable,
    ClassVar,
    Dict,
    List,
    Mapping,
    Optional,
    Sequence,
    Tuple,
    Union,
    cast,
)

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans


# ---------------------------------------------------------------------------
# Ravel plans: flatten/unflatten compiled once per model structure
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RavelPlan:
    """Cached flatten/unflatten layout for one pytree structure.

    ``flatten_stack`` ravels a *list* of N structurally-identical trees
    into one contiguous fp32 ``(N, L)`` buffer in a single jitted call;
    ``unflatten`` restores an ``(L,)`` vector to the original treedef,
    shapes, and per-leaf dtypes.  Both are traced exactly once per model
    structure (the plan is cached), so the per-round cost is pure data
    movement.  ``signature`` is a stable digest of the structure key
    (treedef + shapes + dtypes) — the cheap equality token
    :class:`PartialSum` carries so a parent aggregator can validate a
    regional partial against its own plan without shipping treedefs.
    """

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[Any, ...]
    sizes: Tuple[int, ...]
    total_elems: int
    signature: str
    flatten: Callable[[Any], Any]
    flatten_stack: Callable[[Sequence[Any]], Any]
    unflatten: Callable[[Any], Any]


# Bounded LRU: hierarchical / multi-model serving churns tree structures,
# so an unbounded module-global would grow forever (each plan pins two
# jitted closures) and leak across engines.  Hits move the plan to the
# back; inserts evict from the front.  Plans held by live aggregators
# survive eviction — only the cache entry (and its reuse) is dropped.
# Holds both full RavelPlans (keyed by structure) and GroupPlans (keyed
# by (structure, ("group", leaf indices))) — the composite key is what
# keeps two schemas' masked subtrees of the same tree from colliding.
_PLAN_CACHE: "OrderedDict[Any, Any]" = OrderedDict()
_PLAN_CACHE_MAX: int = 64


def _structure_key(tree: Any) -> Any:
    leaves, treedef = jax.tree.flatten(tree)
    return (
        treedef,
        tuple(tuple(l.shape) for l in leaves),
        tuple(jnp.result_type(l).name for l in leaves),
    )


def clear_plan_cache() -> None:
    """Drop every cached :class:`RavelPlan` (tests / structure churn)."""
    _PLAN_CACHE.clear()


def plan_cache_size() -> int:
    """Number of plans currently cached (bounded by the LRU limit)."""
    return len(_PLAN_CACHE)


def set_plan_cache_limit(max_plans: int) -> int:
    """Set the LRU bound on the module-global plan cache; returns it.

    Shrinking below the current population evicts oldest-first
    immediately.  The default (64) covers dozens of concurrently-served
    model structures; raise it for multi-model zoos, lower it in
    memory-tight tests."""
    global _PLAN_CACHE_MAX
    if max_plans < 1:
        raise ValueError("plan cache limit must be >= 1")
    _PLAN_CACHE_MAX = int(max_plans)
    while len(_PLAN_CACHE) > _PLAN_CACHE_MAX:
        _PLAN_CACHE.popitem(last=False)
    return _PLAN_CACHE_MAX


def plan_for(tree: Any) -> RavelPlan:
    """Return the (LRU-cached) RavelPlan for ``tree``'s structure."""
    key = _structure_key(tree)
    plan = _PLAN_CACHE.get(key)
    if plan is not None:
        _PLAN_CACHE.move_to_end(key)
        return cast(RavelPlan, plan)

    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        raise ValueError("cannot build a ravel plan for an empty pytree")
    shapes = tuple(tuple(l.shape) for l in leaves)
    dtypes = tuple(jnp.result_type(l) for l in leaves)
    sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
    total = int(sum(sizes))
    signature = hashlib.sha1(repr(key).encode()).hexdigest()[:16]

    def flatten(t: Any) -> Any:
        ls = jax.tree.leaves(t)
        return jnp.concatenate([jnp.ravel(l).astype(jnp.float32) for l in ls])

    def flatten_stack(trees: Sequence[Any]) -> Any:
        rows = [
            jnp.concatenate([jnp.ravel(l).astype(jnp.float32) for l in jax.tree.leaves(t)])
            for t in trees
        ]
        return jnp.stack(rows)

    def unflatten(vec: Any) -> Any:
        outs = []
        off = 0
        for shape, dtype, size in zip(shapes, dtypes, sizes):
            # The barrier keeps each slice a slice.  Without it the TPU
            # compiler rewrites slice+reshape of a (4096, 2) leaf into a
            # reshape of the WHOLE vector to (L/2, 2), tiled (8, 128):
            # 64x padding, 34 GB for VGG16 on a 16 GB v5e.
            leaf = jax.lax.optimization_barrier(vec[off:off + size])
            outs.append(leaf.reshape(shape).astype(dtype))
            off += size
        return jax.tree.unflatten(treedef, outs)

    plan = RavelPlan(
        treedef=treedef, shapes=shapes, dtypes=dtypes, sizes=sizes,
        total_elems=total, signature=signature,
        flatten=jax.jit(flatten), flatten_stack=jax.jit(flatten_stack),
        unflatten=jax.jit(unflatten),
    )
    _PLAN_CACHE[key] = plan
    while len(_PLAN_CACHE) > _PLAN_CACHE_MAX:
        _PLAN_CACHE.popitem(last=False)
    return plan


# ---------------------------------------------------------------------------
# Structure validation (typed errors instead of opaque tree.map failures)
# ---------------------------------------------------------------------------

class StructureMismatchError(ValueError):
    """A client's update pytree diverges from the fold's structure.

    Raised (instead of an opaque ``jax.tree.map`` error — or worse, a
    silent broadcast) the moment a second client's treedef or leaf
    shapes fail to match the structure the fold was pinned to.  Carries
    the offending ``client_id`` (when the caller supplied one) and the
    first mismatching leaf ``path``."""

    def __init__(
        self,
        message: str,
        client_id: Optional[str] = None,
        path: Optional[str] = None,
    ) -> None:
        super().__init__(message)
        self.client_id = client_id
        self.path = path


def _leaf_paths(treedef: Any) -> List[str]:
    """Human-readable key paths for every leaf slot of a treedef."""
    dummy = jax.tree.unflatten(treedef, list(range(treedef.num_leaves)))
    kps, _ = jax.tree_util.tree_flatten_with_path(dummy)
    return [jax.tree_util.keystr(kp) or "<root>" for kp, _ in kps]


def _first_structure_mismatch(
    ref_treedef: Any,
    ref_shapes: Tuple[Tuple[int, ...], ...],
    params: Any,
) -> Optional[Tuple[str, str]]:
    """``(leaf path, detail)`` of the first divergence, or None if the
    update matches the reference treedef + leaf shapes (dtypes are NOT
    compared: mixed-precision clients fold through the fp32 cast)."""
    leaves, treedef = jax.tree.flatten(params)
    shapes = tuple(tuple(np.shape(l)) for l in leaves)
    if treedef == ref_treedef:
        if shapes == ref_shapes:
            return None
        for path, got, want in zip(_leaf_paths(treedef), shapes, ref_shapes):
            if got != want:
                return path, f"leaf shape {got} != expected {want}"
        return "<root>", "leaf shapes diverge"
    ref_paths = _leaf_paths(ref_treedef)
    got_paths = _leaf_paths(treedef)
    for rp, gp in zip(ref_paths, got_paths):
        if rp != gp:
            return gp, f"unexpected leaf (expected {rp} here)"
    if len(got_paths) != len(ref_paths):
        longer = got_paths if len(got_paths) > len(ref_paths) else ref_paths
        extra = longer[min(len(got_paths), len(ref_paths))]
        kind = "extra" if len(got_paths) > len(ref_paths) else "missing"
        return extra, (
            f"{kind} leaf: update has {len(got_paths)} leaves, "
            f"expected {len(ref_paths)}"
        )
    return "<root>", f"treedef {treedef} != expected {ref_treedef}"


def _raise_structure_mismatch(
    mismatch: Tuple[str, str], client_id: Optional[str]
) -> None:
    path, detail = mismatch
    who = f"client {client_id!r}" if client_id is not None else "an update"
    raise StructureMismatchError(
        f"update from {who} does not match the fold's pytree structure "
        f"at leaf {path!r}: {detail}",
        client_id=client_id,
        path=path,
    )


# ---------------------------------------------------------------------------
# Update schemas: named parameter groups over one model structure
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GroupPlan:
    """Cached flatten layout for one named subset of a tree's leaves.

    The structured analogue of :class:`RavelPlan`: ``flatten`` ravels
    the *selected* leaves of a full tree (in full-plan leaf order) into
    one compact fp32 ``(total_elems,)`` vector, and ``offsets`` maps
    each compact position back into the full flat vector so a finalize
    can scatter per-group accumulators into one model-sized numerator.
    ``padded_len`` rounds the compact length up to the Pallas BLOCK
    multiple (== the compression QBLOCK), so per-group int8/fp16 deltas
    feed the fused dequantize-and-fold kernel exactly like whole-model
    ones.  ``signature`` digests (full-plan signature, leaf indices) —
    the equality token per-group partial sums carry."""

    leaf_indices: Tuple[int, ...]
    sizes: Tuple[int, ...]
    total_elems: int
    padded_len: int
    signature: str
    offsets: Any  # np.int32 positions in the full flat vector
    flatten: Callable[[Any], Any]


def group_plan_for(tree: Any, leaf_indices: Sequence[int]) -> GroupPlan:
    """The (LRU-cached) :class:`GroupPlan` for a subset of ``tree``'s leaves.

    Cached in the same bounded LRU as full ravel plans, but keyed by
    ``(structure, ("group", indices))`` — two schemas selecting different
    subtrees of one structure get *distinct* plans (and distinct
    signatures), never a colliding cache slot."""
    full = plan_for(tree)
    idx = tuple(sorted(int(i) for i in leaf_indices))
    if not idx:
        raise ValueError("a parameter group must select at least one leaf")
    if len(set(idx)) != len(idx):
        raise ValueError(f"duplicate leaf indices in group selection: {idx}")
    if idx[0] < 0 or idx[-1] >= len(full.sizes):
        raise ValueError(
            f"group leaf indices {idx} out of range for a "
            f"{len(full.sizes)}-leaf structure"
        )
    key = (_structure_key(tree), ("group", idx))
    cached = _PLAN_CACHE.get(key)
    if cached is not None:
        _PLAN_CACHE.move_to_end(key)
        return cast(GroupPlan, cached)

    from repro.kernels.fedavg_reduce import BLOCK as _block

    sizes = tuple(int(full.sizes[i]) for i in idx)
    total = int(sum(sizes))
    padded = -(-total // _block) * _block
    signature = hashlib.sha1(
        f"{full.signature}:group:{idx!r}".encode()
    ).hexdigest()[:16]
    starts = np.concatenate(
        [np.zeros(1, np.int64), np.cumsum(np.asarray(full.sizes, np.int64))]
    )
    offsets = np.concatenate(
        [np.arange(starts[i], starts[i] + full.sizes[i], dtype=np.int64)
         for i in idx]
    ).astype(np.int32)

    def flatten(t: Any) -> Any:
        ls = jax.tree.leaves(t)
        return jnp.concatenate(
            [jnp.ravel(ls[i]).astype(jnp.float32) for i in idx]
        )

    plan = GroupPlan(
        leaf_indices=idx, sizes=sizes, total_elems=total, padded_len=padded,
        signature=signature, offsets=offsets, flatten=jax.jit(flatten),
    )
    _PLAN_CACHE[key] = plan
    while len(_PLAN_CACHE) > _PLAN_CACHE_MAX:
        _PLAN_CACHE.popitem(last=False)
    return plan


def _select_leaves(name: str, selector: Any, tree: Any, paths: Sequence[str]) -> Tuple[int, ...]:
    """Leaf indices a group selector picks out of ``tree``.

    Selector forms: a substring matched against the leaf's key path
    (``"lora_"``), a sequence of substrings (any match), a
    ``path -> bool`` callable, or a boolean mask pytree with the same
    leaf count as the model (truthy leaf = selected)."""
    if isinstance(selector, str):
        return tuple(i for i, p in enumerate(paths) if selector in p)
    if isinstance(selector, (list, tuple)) and all(
        isinstance(s, str) for s in selector
    ):
        toks = list(selector)
        return tuple(
            i for i, p in enumerate(paths) if any(t in p for t in toks)
        )
    if callable(selector):
        return tuple(i for i, p in enumerate(paths) if bool(selector(p)))
    mask_leaves = jax.tree.leaves(selector)
    if len(mask_leaves) != len(paths):
        raise ValueError(
            f"schema group {name!r}: boolean mask has {len(mask_leaves)} "
            f"leaves, the model has {len(paths)}"
        )
    return tuple(i for i, m in enumerate(mask_leaves) if bool(np.all(m)))


class UpdateSchema:
    """Named parameter groups over one model structure (order preserved).

    The first-class description of a *structured* update: each group
    names a subset of the model's leaves (see :func:`_select_leaves` for
    selector forms), and clients may ship any subset of the groups —
    silos absent from a group contribute no weight to it.  Groups may
    overlap; an element covered by several groups normalizes by the sum
    of the covering groups' weight totals.  ``resolve(tree)`` binds the
    schema to a concrete structure, building (cached) per-group plans.
    """

    def __init__(
        self,
        groups: Union[Mapping[str, Any], Sequence[Tuple[str, Any]]],
    ) -> None:
        items: List[Tuple[str, Any]]
        if isinstance(groups, Mapping):
            items = [(str(n), s) for n, s in groups.items()]
        else:
            items = [(str(n), s) for n, s in groups]
        if not items:
            raise ValueError("an UpdateSchema needs at least one group")
        names = [n for n, _ in items]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate group names in schema: {names}")
        for n, sel in items:
            if sel is None:
                raise ValueError(
                    f"schema group {n!r} has no selector (None)"
                )
        self.groups: Tuple[Tuple[str, Any], ...] = tuple(items)

    @property
    def group_names(self) -> Tuple[str, ...]:
        return tuple(n for n, _ in self.groups)

    def __repr__(self) -> str:
        return f"UpdateSchema({', '.join(self.group_names)})"

    def resolve(self, tree: Any) -> "ResolvedSchema":
        """Bind the schema to ``tree``'s structure (per-group plans)."""
        full = plan_for(tree)
        paths = _leaf_paths(full.treedef)
        resolved: List[Tuple[str, GroupPlan]] = []
        for name, sel in self.groups:
            idx = _select_leaves(name, sel, tree, paths)
            if not idx:
                raise ValueError(
                    f"schema group {name!r} selects no leaves of the model "
                    f"(selector {sel!r}; leaf paths: {paths[:8]}...)"
                )
            resolved.append((name, group_plan_for(tree, idx)))
        leaf_groups = tuple(
            tuple(n for n, gp in resolved if i in set(gp.leaf_indices))
            for i in range(len(full.sizes))
        )
        signature = hashlib.sha1(
            (full.signature + "".join(
                f"|{n}:{gp.signature}" for n, gp in resolved
            )).encode()
        ).hexdigest()[:16]
        return ResolvedSchema(
            plan=full, groups=tuple(resolved), signature=signature,
            leaf_groups=leaf_groups,
        )


def as_update_schema(
    spec: Union[None, "UpdateSchema", Mapping[str, Any]],
) -> Optional["UpdateSchema"]:
    """Coerce a user-facing schema knob into an :class:`UpdateSchema`.

    Accepts ``None`` (off), an existing schema, or a mapping of group
    name -> selector.  Raises ``ValueError`` on anything else — the
    builder calls this at configuration time so bad knobs fail before
    any round runs."""
    if spec is None:
        return None
    if isinstance(spec, UpdateSchema):
        return spec
    if isinstance(spec, Mapping):
        return UpdateSchema(spec)
    raise ValueError(
        f"schema must be None, an UpdateSchema, or a mapping of group "
        f"name -> selector; got {type(spec).__name__}"
    )


@dataclasses.dataclass(frozen=True)
class ResolvedSchema:
    """An :class:`UpdateSchema` bound to one concrete model structure.

    ``leaf_groups[i]`` names the groups covering leaf ``i`` (in schema
    order) — the coverage map the structured finalize normalizes with.
    ``signature`` digests the full plan plus every group's plan, so two
    endpoints agreeing on a signature agree on the exact partition."""

    plan: RavelPlan
    groups: Tuple[Tuple[str, GroupPlan], ...]
    signature: str
    leaf_groups: Tuple[Tuple[str, ...], ...]

    @property
    def group_names(self) -> Tuple[str, ...]:
        return tuple(n for n, _ in self.groups)

    def group(self, name: str) -> GroupPlan:
        for n, gp in self.groups:
            if n == name:
                return gp
        raise KeyError(f"schema has no group {name!r}")

    @property
    def full_coverage(self) -> bool:
        """Every leaf in exactly one group (the dense-equivalent case)."""
        return all(len(gs) == 1 for gs in self.leaf_groups)

    @property
    def covered(self) -> bool:
        """Every leaf in at least one group."""
        return all(len(gs) >= 1 for gs in self.leaf_groups)

    @property
    def disjoint(self) -> bool:
        """No leaf in more than one group."""
        return all(len(gs) <= 1 for gs in self.leaf_groups)


# ---------------------------------------------------------------------------
# Fused flat reduces
# ---------------------------------------------------------------------------

def _dot_reduce(stacked: Any, w: Any) -> Any:
    """(N, L) x (N,) -> (L,): single fp32-accumulated contraction.

    ``w`` must already be normalized."""
    out = jnp.einsum("n,nl->l", w, stacked, preferred_element_type=jnp.float32)
    return out.astype(stacked.dtype)


def _pallas_flat_reduce(stacked: Any, weights: Any, interpret: Any) -> Any:
    from repro.kernels.fedavg_reduce import fedavg_reduce as _kernel
    return _kernel(stacked, weights, interpret=interpret)


def fused_stacked_tree_reduce(stacked: Any, weights: Any) -> Any:
    """Traceable FedAvg over a pytree with a leading client/pod axis.

    Flattens every leaf of the replica stack into one ``(N, L)`` buffer
    and reduces it with a single fused contraction (Pallas kernel on
    TPU, fp32 einsum elsewhere) instead of a per-leaf ``tree.map`` —
    this is the fused call `pod_fedavg` lowers inside `fl_round_step`.
    """
    leaves, treedef = jax.tree.flatten(stacked)
    if not leaves:
        return stacked
    n = leaves[0].shape[0]
    w = weights.astype(jnp.float32)
    flat = jnp.concatenate(
        [l.reshape(n, -1).astype(jnp.float32) for l in leaves], axis=1
    )
    if jax.default_backend() == "tpu":
        red = _pallas_flat_reduce(flat, w, interpret=False)
    else:
        red = _dot_reduce(flat, w / jnp.sum(w))
    outs = []
    off = 0
    for l in leaves:
        size = int(np.prod(l.shape[1:])) if l.ndim > 1 else 1
        outs.append(red[off:off + size].reshape(l.shape[1:]).astype(l.dtype))
        off += size
    return jax.tree.unflatten(treedef, outs)


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class AggStats:
    """Engine counters: `n_traces` counts XLA retraces (a steady-state
    round must hit the jit cache, i.e. n_traces stays flat while n_calls
    grows).  Byte volume is tracked on two axes that diverge once updates
    arrive compressed: ``wire_bytes`` is what actually crossed the
    transport (the compressed frame), ``folded_bytes`` the dense fp32
    equivalent the reduce is worth (for GB/s accounting).  For dense
    updates the two are equal."""

    n_calls: int = 0
    n_traces: int = 0
    last_wire_bytes: int = 0
    total_wire_bytes: int = 0
    last_folded_bytes: int = 0
    total_folded_bytes: int = 0

    def record(self, folded: int, wire: Optional[int] = None) -> None:
        """Account one update: dense-equivalent bytes, and wire bytes if
        they differ (``wire=None`` means the update arrived dense)."""
        w = folded if wire is None else wire
        self.last_wire_bytes = w
        self.total_wire_bytes += w
        self.last_folded_bytes = folded
        self.total_folded_bytes += folded

    # Back-compat aliases: `last_bytes`/`total_bytes` always meant the
    # dense in-memory volume of the reduce, which is the folded axis.
    @property
    def last_bytes(self) -> int:
        return self.last_folded_bytes

    @property
    def total_bytes(self) -> int:
        return self.total_folded_bytes


class AggregationEngine:
    """Backend-aware fused FedAvg reducer with cached per-model plans.

    Parameters
    ----------
    backend : override ``jax.default_backend()`` ("tpu" enables the
        flatten-once + Pallas + donation path).
    use_pallas : force the kernel path on/off (defaults to backend=="tpu").
    interpret : explicit Pallas interpret-mode override (tests); None
        defers to backend detection in `kernels.ops`.
    chunk_elems : if set, `reduce_flat` streams in column blocks of this
        many elements (O(N·block) working memory).
    """

    def __init__(
        self,
        backend: Optional[str] = None,
        use_pallas: Optional[bool] = None,
        interpret: Optional[bool] = None,
        chunk_elems: Optional[int] = None,
    ) -> None:
        self.backend = backend if backend is not None else jax.default_backend()
        self.use_pallas = (self.backend == "tpu") if use_pallas is None else use_pallas
        self.interpret = interpret
        self.chunk_elems = chunk_elems
        self.stats = AggStats()
        self._tree_reduce_cache: Dict[Any, Callable[..., Any]] = {}

    # -- weights -------------------------------------------------------------
    @staticmethod
    def _normalized_weights(weights: Sequence[float]) -> Any:
        w = np.asarray(weights, np.float64)
        if w.ndim != 1 or w.size == 0:
            raise ValueError("weights must be a non-empty 1-D sequence")
        if w.sum() <= 0:
            raise ValueError("aggregation weights must sum to a positive value")
        return (w / w.sum()).astype(np.float32)

    # -- tree path (FLServer hot path) ---------------------------------------
    def aggregate(self, client_params: Sequence[Any], weights: Sequence[float]) -> Any:
        """Weighted average of N client pytrees in one fused call.

        Numerically equivalent to the `aggregation.fedavg` oracle (fp32
        accumulation, cast back to each leaf's dtype) but with exactly
        one pass over the client bytes per round.
        """
        w = self._normalized_weights(weights)
        if len(client_params) != w.size:
            raise ValueError("len(client_params) != len(weights)")
        self.stats.n_calls += 1
        nbytes = sum(l.nbytes for t in client_params for l in jax.tree.leaves(t))
        self.stats.record(nbytes)

        if self.use_pallas:
            plan = plan_for(client_params[0])
            stacked = plan.flatten_stack(list(client_params))
            red = self.reduce_flat(stacked, jnp.asarray(w))
            return plan.unflatten(red)

        fn = self._get_tree_reduce(client_params)
        return fn(list(client_params), jnp.asarray(w))

    def _get_tree_reduce(self, client_params: Sequence[Any]) -> Callable[..., Any]:
        key = (len(client_params), _structure_key(client_params[0]))
        fn = self._tree_reduce_cache.get(key)
        if fn is not None:
            return fn
        stats = self.stats

        def tree_reduce(trees: Any, w: Any) -> Any:
            stats.n_traces += 1  # executes at trace time only

            def avg(*leaves: Any) -> Any:
                acc = leaves[0].astype(jnp.float32) * w[0]
                for i in range(1, len(leaves)):
                    acc = acc + leaves[i].astype(jnp.float32) * w[i]
                return acc.astype(leaves[0].dtype)

            return jax.tree.map(avg, *trees)

        fn = jax.jit(tree_reduce)
        self._tree_reduce_cache[key] = fn
        return fn

    # -- flat path ((N, L) stacked buffers) ----------------------------------
    def reduce_flat(
        self,
        stacked: Any,
        weights: Any,
        donate: Optional[bool] = None,
        chunk_elems: Optional[int] = None,
    ) -> Any:
        """Weighted average over axis 0 of a contiguous (N, L) buffer.

        ``donate=True`` hands the stacked buffer to XLA (the caller must
        not reuse it); defaults to donating only on the Pallas/TPU path,
        where the buffer would otherwise be duplicated for padding.
        Chunked mode slices the buffer, so donation does not apply there
        (an explicit ``donate=True`` with chunking is an error).
        """
        if stacked.ndim != 2:
            raise ValueError(f"expected (N, L) stacked buffer, got {stacked.shape}")
        w = weights.astype(jnp.float32)
        chunk = chunk_elems if chunk_elems is not None else self.chunk_elems
        if chunk:
            if donate:
                raise ValueError("chunked reduce slices the buffer; donation "
                                 "does not apply (pass donate=False/None)")
            return self._reduce_flat_chunked(stacked, w, int(chunk))
        if donate is None:
            donate = self.use_pallas and self.backend == "tpu"
        return self._get_flat_reduce(donate)(stacked, w)

    def _get_flat_reduce(self, donate: bool) -> Callable[..., Any]:
        """Per-engine jitted flat reduce (trace-counted, backend-routed)."""
        key = ("flat", self.use_pallas, bool(donate))
        fn = self._tree_reduce_cache.get(key)
        if fn is not None:
            return fn
        stats = self.stats
        if self.use_pallas:
            interp = self.interpret
            if interp is None:
                from repro.kernels.ops import _interpret_default
                interp = _interpret_default()

            def flat_reduce(stacked: Any, w: Any) -> Any:
                stats.n_traces += 1  # executes at trace time only
                return _pallas_flat_reduce(stacked, w, interpret=interp)
        else:
            def flat_reduce(stacked: Any, w: Any) -> Any:
                stats.n_traces += 1  # executes at trace time only
                return _dot_reduce(stacked, w / jnp.sum(w))

        fn = jax.jit(flat_reduce, donate_argnums=(0,) if donate else ())
        self._tree_reduce_cache[key] = fn
        return fn

    def _reduce_flat_chunked(self, stacked: Any, w: Any, chunk: int) -> Any:
        """Column-blocked streaming reduce: O(N*chunk) working set.

        Each block goes through the same backend-routed reduce as the
        unchunked path (Pallas kernel when use_pallas, einsum otherwise)."""
        _, L = stacked.shape
        fn = self._get_flat_reduce(donate=False)
        outs = [fn(stacked[:, off:off + chunk], w) for off in range(0, L, chunk)]
        return jnp.concatenate(outs) if len(outs) > 1 else outs[0]

    # -- streaming -----------------------------------------------------------
    def streaming(
        self,
        base: Any = None,
        base_round: Optional[int] = None,
        schema: Union[None, "UpdateSchema", "ResolvedSchema", Mapping[str, Any]] = None,
    ) -> Union["StreamingAggregator", "StructuredStreamingAggregator"]:
        """New per-round streaming accumulator (async client folding).

        ``base`` switches the aggregator to flat/delta mode anchored on
        the round's global weights — required to fold
        :class:`~repro.federated.compression.CompressedUpdate` payloads
        (deltas against ``base``) and numerically identical to the plain
        weighted average for dense updates (the base cancels exactly).
        ``base_round`` tags the base so compressed updates carrying a
        ``base_round`` of their own are validated against it (a delta
        folded against the wrong round's base is silent corruption —
        see :meth:`StreamingAggregator.rebase`).

        ``schema`` switches to *structured* mode: per-group accumulators
        under an :class:`UpdateSchema` (named parameter groups), folding
        partial updates with per-group weight normalization — see
        :class:`StructuredStreamingAggregator`.  Structured mode needs
        ``base`` (absent groups keep the base's values)."""
        if schema is not None:
            if base is None:
                raise ValueError(
                    "streaming(schema=...) needs base=global_params: absent "
                    "groups and per-group deltas are defined relative to it"
                )
            return StructuredStreamingAggregator(
                self, schema, base, base_round=base_round
            )
        return StreamingAggregator(self, base=base, base_round=base_round)


# ---------------------------------------------------------------------------
# Streaming / incremental accumulation
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CarryEntry:
    """One late ``c_msg_train`` buffered for a later round's average.

    The update was computed against ``origin_round``'s global weights; when
    it is finally folded, its example weight is discounted by the staleness
    factor ``discount ** (fold_round - origin_round)`` so fresh silos
    dominate while the straggler's contribution still lands (never silently
    dropped).

    ``params`` must be a *dense* pytree: a compressed update encodes a
    delta against its origin round's base, which a later round no longer
    has — the async engine dequantizes at park time
    (:func:`repro.federated.compression.materialize_update`) so the
    parked value is base-independent."""

    client_id: str
    params: Any
    weight: float       # raw example weight (n_samples), undiscounted
    origin_round: int   # round whose deadline the message missed
    late_by_s: float = 0.0  # virtual seconds past that round's deadline
    # ||update - origin base||_2 at park time, when the engine had a base
    # to measure against; lets DriftAwareDiscount compare how far the
    # global model has since moved relative to the parked update's own
    # step size.  None = not measured (dense park without a base).
    origin_delta_norm: Optional[float] = None

    def age_at(self, round_idx: int) -> int:
        """Rounds of staleness when folded in ``round_idx`` (floor 1).

        The single source of the age rule — `fold_carry` and the async
        round engine's timed drain both discount by ``discount**age_at``."""
        return max(1, round_idx - self.origin_round)


class CarryOverBuffer:
    """Late updates parked between rounds (deadline-driven partial rounds).

    The async round engine defers any ``c_msg_train`` that misses its
    round's ``T_round`` deadline into this buffer; the next round's
    :class:`StreamingAggregator` drains it first (the messages are already
    on the server), folding each entry with a staleness-discounted weight.
    """

    def __init__(self) -> None:
        self._entries: List[CarryEntry] = []

    def defer(self, entry: CarryEntry) -> None:
        self._entries.append(entry)

    def drain(self) -> List[CarryEntry]:
        entries, self._entries = self._entries, []
        return entries

    def clients(self) -> List[str]:
        return [e.client_id for e in self._entries]

    def snapshot(self) -> List[CarryEntry]:
        """Non-destructive view of the parked entries (oldest first)."""
        return list(self._entries)

    def pending_weight(self) -> float:
        """Total raw (undiscounted) example weight awaiting a fold."""
        return sum(e.weight for e in self._entries)

    def __len__(self) -> int:
        return len(self._entries)

    def __bool__(self) -> bool:
        return bool(self._entries)


# ---------------------------------------------------------------------------
# Staleness policies: how much weight a carried-over update keeps
# ---------------------------------------------------------------------------

class StalenessPolicy:
    """How much of a parked update's weight survives a late fold.

    ``effective_multiplier`` maps one :class:`CarryEntry` to the factor
    its raw example weight is scaled by when finally folded in
    ``round_idx``.  Policies advertising ``uses_drift`` additionally
    receive ``drift`` — the ratio of how far the global model has moved
    since the update was parked to the update's own step size — so the
    discount can track *observed* divergence rather than just age."""

    uses_drift: ClassVar[bool] = False

    def effective_multiplier(
        self,
        entry: CarryEntry,
        round_idx: int,
        drift: Optional[float] = None,
    ) -> float:
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class AgeDiscount(StalenessPolicy):
    """The PR-3 rule: ``discount ** age`` with age floored at 1 round.

    Bit-identical to :meth:`StreamingAggregator.add_stale`'s arithmetic
    (same ``float(discount) ** int(age)`` expression), so swapping the
    default policy in changes nothing for existing runs."""

    discount: float = 0.5

    def effective_multiplier(
        self,
        entry: CarryEntry,
        round_idx: int,
        drift: Optional[float] = None,
    ) -> float:
        return float(self.discount) ** int(entry.age_at(round_idx))


@dataclasses.dataclass(frozen=True)
class DriftAwareDiscount(StalenessPolicy):
    """Convergence-aware staleness: decay by observed update drift.

    Starts from the same age discount, then divides by
    ``1 + drift_coef * (drift - 1)`` when the model has drifted *more*
    than the parked update's own step (``drift > 1``) — a late update
    pointing at a distant past model is down-weighted harder than its
    age alone implies.  When drift is small (the model barely moved, so
    the stale direction is still informative) or unmeasurable (no base
    at park time), the policy reduces exactly to :class:`AgeDiscount`.
    """

    discount: float = 0.5
    drift_coef: float = 1.0

    uses_drift: ClassVar[bool] = True

    def effective_multiplier(
        self,
        entry: CarryEntry,
        round_idx: int,
        drift: Optional[float] = None,
    ) -> float:
        base = float(self.discount) ** int(entry.age_at(round_idx))
        if drift is None or drift <= 1.0:
            return base
        return base / (1.0 + float(self.drift_coef) * (float(drift) - 1.0))


def _scale_tree_impl(tree: Any, w: Any) -> Any:
    return jax.tree.map(lambda l: l.astype(jnp.float32) * w, tree)


_scale_tree: Callable[..., Any] = jax.jit(_scale_tree_impl)


# The accumulator is donated: same shape/dtype in and out, so XLA updates
# it in place — O(L) extra memory total, regardless of client count.
def _accum_tree_impl(acc: Any, tree: Any, w: Any) -> Any:
    return jax.tree.map(lambda a, l: a + l.astype(jnp.float32) * w, acc, tree)


_accum_tree: Callable[..., Any] = jax.jit(_accum_tree_impl, donate_argnums=(0,))


def _scale_acc_impl(acc: Any, inv: Any) -> Any:
    return jax.tree.map(lambda a: a * inv, acc)


_scale_acc: Callable[..., Any] = jax.jit(_scale_acc_impl, donate_argnums=(0,))


# Flat-mode (delta) folds: the padded fp32 accumulator is donated so XLA
# updates it in place, exactly like the tree-mode `_accum_tree`.
def _flat_delta_fold_impl(acc: Any, flat: Any, base: Any, w: Any) -> Any:
    """acc[:L] += (flat - base) * w — dense update folded as a delta."""
    return acc.at[: base.shape[0]].add((flat - base) * w)


_flat_delta_fold: Callable[..., Any] = jax.jit(
    _flat_delta_fold_impl, donate_argnums=(0,)
)


def _flat_scatter_fold_impl(acc: Any, idx: Any, vals: Any, w: Any) -> Any:
    """acc[idx] += vals * w — the top-k sparse fold (fp16 values)."""
    return acc.at[idx].add(vals.astype(jnp.float32) * w)


_flat_scatter_fold: Callable[..., Any] = jax.jit(
    _flat_scatter_fold_impl, donate_argnums=(0,)
)


def _flat_dequant_fold_jnp_impl(acc: Any, data: Any, scales: Any, w: Any) -> Any:
    """Fused dequantize-and-fold for einsum-tier backends: one jitted
    pass, same per-block math as the Pallas `dequant_fold` kernel."""
    nb = scales.shape[0]
    x = data.reshape(nb, -1).astype(jnp.float32)
    return acc + ((w * scales)[:, None] * x).reshape(acc.shape)


_flat_dequant_fold_jnp: Callable[..., Any] = jax.jit(
    _flat_dequant_fold_jnp_impl, donate_argnums=(0,)
)


# A regional partial sum is another padded fp32 accumulator: folding it
# is a donated elementwise add (partial sums compose associatively).
def _flat_partial_fold_impl(acc: Any, other: Any) -> Any:
    """acc += other — fold a regional partial accumulator in."""
    return acc + other


_flat_partial_fold: Callable[..., Any] = jax.jit(
    _flat_partial_fold_impl, donate_argnums=(0,)
)


def _flat_finalize_impl(acc: Any, base: Any, inv: Any) -> Any:
    """base + acc[:L] * inv — the flat-mode weighted average.  The padded
    accumulator is NOT donated here: the (L,) output can't alias it."""
    return base + acc[: base.shape[0]] * inv


_flat_finalize: Callable[..., Any] = jax.jit(_flat_finalize_impl)


# Structured-finalize helpers: per-group compact accumulators scatter
# into one model-sized numerator (exact: every target starts at 0, so
# the scatter-add is 0 + x), then normalize elementwise by each leaf's
# covering-group weight total.
def _flat_group_scatter_impl(num: Any, idx: Any, vals: Any) -> Any:
    """num[idx] += vals — place a group's compact accumulator."""
    return num.at[idx].add(vals)


_flat_group_scatter: Callable[..., Any] = jax.jit(
    _flat_group_scatter_impl, donate_argnums=(0,)
)


def _flat_finalize_vec_impl(num: Any, base: Any, inv: Any) -> Any:
    """base + num * inv — elementwise normalizer (uncovered / weightless
    elements carry inv == 0 and keep the base exactly).  Not donated:
    the output aliases neither input."""
    return base + num * inv


_flat_finalize_vec: Callable[..., Any] = jax.jit(_flat_finalize_vec_impl)


def _fold_compressed_into(
    acc: Any,
    update: Any,
    w: float,
    padded_len: int,
    use_pallas: bool,
    interpret: Optional[bool],
) -> Any:
    """Fold one CompressedUpdate's delta into a padded fp32 accumulator.

    The single codec-dispatch used by both the whole-model
    :meth:`StreamingAggregator.add_compressed` and the per-group
    structured fold — identical ops on identical layouts, which is what
    makes a full-coverage structured fold bit-for-bit equal to the dense
    one.  ``acc`` is donated by the underlying jitted folds; callers
    must rebind to the return value."""
    if update.codec == "topk":
        idx, vals = _to_device(np.asarray(update.indices), np.asarray(update.data))
        return _flat_scatter_fold(acc, idx, vals, jnp.float32(w))
    if update.codec in ("int8", "fp16"):
        from repro.federated.compression import QBLOCK
        nb = padded_len // QBLOCK
        data = np.zeros(padded_len, dtype=update.data.dtype)
        data[: update.total_elems] = update.data
        if update.codec == "int8":
            scales = np.asarray(update.scales, np.float32)
            if scales.shape != (nb,):
                raise ValueError(
                    f"int8 update has {scales.shape} scales; expected ({nb},)"
                )
        else:
            scales = np.ones(nb, np.float32)
        data_d, scales_d = _to_device(data, scales)
        if use_pallas:
            from repro.kernels.fedavg_reduce import dequant_fold
            return dequant_fold(
                acc, data_d, scales_d, jnp.float32(w), interpret=interpret,
            )
        return _flat_dequant_fold_jnp(acc, data_d, scales_d, jnp.float32(w))
    raise ValueError(f"unknown compressed codec {update.codec!r}")


def _to_device(*arrays: np.ndarray) -> List[Any]:
    """A compressed payload's host arrays onto the device.  Counters
    ``h2d_s`` (host side of the copies) and ``h2d_bytes``."""
    t0 = time.perf_counter()
    out = [jnp.asarray(a) for a in arrays]
    spans.add("h2d_s", time.perf_counter() - t0)
    spans.add("h2d_bytes", sum(a.nbytes for a in arrays))
    return out


def _leaf_nbytes(leaf: Any) -> int:
    nbytes = getattr(leaf, "nbytes", None)
    return int(nbytes) if nbytes is not None else int(np.asarray(leaf).nbytes)


# ---------------------------------------------------------------------------
# Partial sums (hierarchical aggregation)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PartialSum:
    """One aggregator's exported partial fold — the hierarchy wire unit.

    ``acc`` is the BLOCK-padded fp32 delta accumulator (the exact buffer
    a flat-mode :class:`StreamingAggregator` holds: ``sum_i w_i *
    (update_i - base)``, zero-padded to the Pallas tile multiple), so a
    parent engine folds it with one elementwise add and regional /
    parent results compose to the same weighted average the flat fold
    computes.  ``wsum`` / ``n_clients`` are the region's raw weight
    total and client count; ``plan_signature`` pins the model structure
    and ``base_round`` the global weights the deltas were taken against
    — :meth:`StreamingAggregator.fold_partial` validates both, because a
    partial folded against a different structure or base is silent
    corruption."""

    acc: Any
    wsum: float
    n_clients: int
    plan_signature: str
    base_round: Optional[int] = None
    region_id: str = ""

    @property
    def wire_bytes(self) -> int:
        """Bytes a parent link carries for this partial (the fp32 acc)."""
        return _leaf_nbytes(self.acc)


@dataclasses.dataclass(frozen=True)
class StructuredPartialSum:
    """A structured aggregator's exported fold: one PartialSum per group.

    Groups no silo in the region contributed to are *omitted* — absent
    silos contribute no weight to a group, and that has to survive the
    hierarchy hop (a zero-accumulator partial with nonzero wsum would
    drag the group toward the base).  ``schema_signature`` pins the
    exact partition; each group's inner :class:`PartialSum` carries its
    own group-plan signature, and the parent validates both."""

    groups: Tuple[Tuple[str, PartialSum], ...]
    schema_signature: str
    n_clients: int
    base_round: Optional[int] = None
    region_id: str = ""

    @property
    def wire_bytes(self) -> int:
        """Bytes a parent link carries (sum of the per-group fp32 accs)."""
        return sum(p.wire_bytes for _, p in self.groups)

    @property
    def wsum(self) -> float:
        """Round-weight proxy for bus/event accounting: the largest
        per-group weight total (each group normalizes independently, so
        there is no single scalar — the max is what a fully-present silo
        cohort contributed)."""
        return max((p.wsum for _, p in self.groups), default=0.0)

    def group_wsums(self) -> Dict[str, float]:
        return {n: p.wsum for n, p in self.groups}


class StreamingAggregator:
    """Running weighted accumulation: fold clients in as they land.

    ``add(params, weight)`` costs one fused pass over that client's
    bytes and keeps only a single fp32 accumulator (donated in place),
    so asynchronously arriving silos are aggregated in O(L) memory
    rather than O(N·L).  ``result()`` normalizes by the running weight
    total, casts back to the model dtypes, consumes the accumulator, and
    resets all per-fold state so a reused aggregator starts a fresh fold.

    With ``base`` (the round's global weights) the aggregator runs in
    *flat/delta mode*: one padded fp32 vector accumulator, every update
    folded as ``w * (update - base)`` and the result read out as
    ``base + acc / wsum`` — numerically the same weighted average (the
    base cancels exactly), but able to fold
    :class:`~repro.federated.compression.CompressedUpdate` payloads
    (int8 / fp16 / top-k deltas) directly via the fused Pallas
    dequantize-and-fold kernel, never materializing a dense fp32 update.

    The base survives ``result()`` so a flat-mode aggregator can be
    reused — but a *reused* aggregator folding the NEXT round's deltas
    must first :meth:`rebase` onto that round's global weights:
    compressed deltas are meaningless against a stale base.  Construct
    with ``base_round`` (or via ``streaming(base=..., base_round=...)``)
    to have :meth:`add_compressed` enforce the match against each
    update's own ``base_round`` tag.
    """

    def __init__(
        self,
        engine: Optional[AggregationEngine] = None,
        base: Any = None,
        base_round: Optional[int] = None,
    ) -> None:
        self._engine = engine
        self._plan: Optional[RavelPlan] = None
        self._base_flat: Optional[Any] = None
        self._padded_len = 0
        self.base_round: Optional[int] = None
        if base is not None:
            from repro.kernels.fedavg_reduce import BLOCK as _block
            self._plan = plan_for(base)
            self._base_flat = self._plan.flatten(base)
            self._padded_len = -(-self._plan.total_elems // _block) * _block
            self.base_round = base_round
        elif base_round is not None:
            raise ValueError(
                "base_round tags a delta base: pass base= too"
            )
        self._acc: Any = None
        self._acc_flat: Optional[Any] = None
        self._dtypes: Optional[List[Any]] = None
        self._treedef: Any = None
        self._shapes: Optional[Tuple[Tuple[int, ...], ...]] = None
        self._wsum = 0.0
        self.n_clients = 0

    def _reset(self) -> None:
        """Clear per-fold state (`result()` calls this); the base/plan
        are construction-time configuration and survive for reuse —
        callers starting a NEW round on a reused flat-mode aggregator
        must :meth:`rebase` onto that round's global weights first."""
        self._acc = None
        self._acc_flat = None
        self._dtypes = None
        self._treedef = None
        self._shapes = None
        self._wsum = 0.0
        self.n_clients = 0

    def _ensure_flat_acc(self) -> Any:
        if self._acc_flat is None:
            self._acc_flat = jnp.zeros(self._padded_len, jnp.float32)
        return self._acc_flat

    @property
    def mid_fold(self) -> bool:
        """True while a fold is accumulating (clients added, no result yet)."""
        return self.n_clients > 0 or self._acc is not None or self._acc_flat is not None

    def rebase(self, base: Any, base_round: Optional[int] = None) -> None:
        """Re-anchor a reused flat-mode aggregator on a new round's base.

        The fix for the stale-base reuse bug: ``_base_flat`` survives
        ``_reset()`` by design (construction-time configuration), so a
        flat-mode aggregator reused for the next round would silently
        fold that round's compressed deltas against the *previous*
        round's global weights.  Call ``rebase(new_global_params,
        base_round=r)`` between rounds instead of rebuilding the
        aggregator; the new base must have the same pytree structure,
        and rebasing mid-fold is rejected (the accumulator holds deltas
        against the old base)."""
        if self._plan is None or self._base_flat is None:
            raise ValueError(
                "rebase() applies to flat/delta mode: construct the "
                "aggregator with streaming(base=global_params) first"
            )
        if self.mid_fold:
            raise ValueError(
                "cannot rebase mid-fold: the accumulator holds deltas "
                "against the current base — call result() (or "
                "export_partial()) first"
            )
        plan = plan_for(base)
        if plan.signature != self._plan.signature:
            mismatch = _first_structure_mismatch(
                self._plan.treedef, self._plan.shapes, base
            )
            raise StructureMismatchError(
                "rebase() base does not match the aggregator's plan"
                + (f" at leaf {mismatch[0]!r}: {mismatch[1]}" if mismatch else ""),
                path=mismatch[0] if mismatch else None,
            )
        self._plan = plan
        self._base_flat = plan.flatten(base)
        self.base_round = base_round

    def _check_structure(self, params: Any, client_id: Optional[str]) -> None:
        if self._plan is not None:
            ref_treedef, ref_shapes = self._plan.treedef, self._plan.shapes
        elif self._treedef is not None and self._shapes is not None:
            ref_treedef, ref_shapes = self._treedef, self._shapes
        else:
            return
        mismatch = _first_structure_mismatch(ref_treedef, ref_shapes, params)
        if mismatch is not None:
            _raise_structure_mismatch(mismatch, client_id)

    def add(
        self,
        params: Any,
        weight: float,
        block: bool = False,
        wire_bytes: Optional[int] = None,
        client_id: Optional[str] = None,
    ) -> None:
        """Fold one client in; ``block=True`` waits for the fused
        accumulate to finish (the async round engine uses it to measure
        the true per-fold cost instead of dispatch latency).
        ``wire_bytes`` is the transport frame size when it differs from
        the dense in-memory bytes (compressed arrivals); compressed
        payloads themselves route to :meth:`add_compressed`.
        ``client_id`` names the silo in structure-mismatch errors."""
        from repro.federated.compression import CompressedUpdate
        if isinstance(params, CompressedUpdate):
            self.add_compressed(
                params, weight, block=block, wire_bytes=wire_bytes,
                client_id=client_id,
            )
            return
        w = float(weight)
        if w < 0:
            raise ValueError("client weight must be non-negative")
        self._check_structure(params, client_id)
        if self._base_flat is not None:
            assert self._plan is not None
            flat = self._plan.flatten(params)
            acc = self._ensure_flat_acc()
            self._acc_flat = _flat_delta_fold(
                acc, flat, self._base_flat, jnp.float32(w)
            )
            folded = self._acc_flat
        elif self._acc is None:
            leaves, self._treedef = jax.tree.flatten(params)
            # Pin accumulator dtypes from the first client's *concrete*
            # leaf dtypes (what jnp.asarray actually stores) — never
            # jnp.result_type, which weak-type-promotes Python-scalar
            # and numpy-default leaves past what jax will materialize.
            self._dtypes = [jnp.asarray(l).dtype for l in leaves]
            # Pin the structure too: every later client is validated
            # against this treedef + these leaf shapes (a mismatch used
            # to surface as an opaque tree.map error or a silent
            # broadcast).
            self._shapes = tuple(tuple(np.shape(l)) for l in leaves)
            self._acc = _scale_tree(params, jnp.float32(w))
            folded = self._acc
        else:
            self._acc = _accum_tree(self._acc, params, jnp.float32(w))
            folded = self._acc
        if block:
            jax.block_until_ready(folded)
        self._wsum += w
        self.n_clients += 1
        if self._engine is not None:
            nbytes = sum(_leaf_nbytes(l) for l in jax.tree.leaves(params))
            self._engine.stats.record(nbytes, wire_bytes)

    def add_compressed(
        self,
        update: Any,
        weight: float,
        block: bool = False,
        wire_bytes: Optional[int] = None,
        client_id: Optional[str] = None,
    ) -> None:
        """Fold one compressed delta straight into the fp32 accumulator.

        int8 / fp16 payloads go through the fused Pallas
        ``dequant_fold`` kernel (or its jitted fallback on einsum-tier
        backends) — one pass over the quantized bytes, no dense fp32
        intermediate; top-k payloads fold with a donated sparse scatter.

        An update tagged with a ``base_round`` must match the
        aggregator's own base-round tag: the payload is a delta against
        that specific round's global weights, and folding it against any
        other base silently corrupts the average (the stale-base reuse
        bug) — :meth:`rebase` the aggregator between rounds.
        """
        if self._base_flat is None or self._plan is None:
            raise ValueError(
                "compressed updates need a delta base: construct the "
                "aggregator with streaming(base=global_params)"
            )
        update_round = getattr(update, "base_round", None)
        if update_round is not None and update_round != self.base_round:
            who = f" from client {client_id!r}" if client_id is not None else ""
            raise ValueError(
                f"compressed update{who} was encoded against base round "
                f"{update_round}, but the aggregator's base is "
                f"{'untagged' if self.base_round is None else f'round {self.base_round}'}"
                " — rebase(new_base, base_round=...) the aggregator onto "
                "the update's round before folding"
            )
        if update.total_elems != self._plan.total_elems:
            raise ValueError(
                f"compressed update has {update.total_elems} elements; "
                f"the model has {self._plan.total_elems}"
            )
        w = float(weight)
        if w < 0:
            raise ValueError("client weight must be non-negative")
        acc = self._ensure_flat_acc()
        interp = self._engine.interpret if self._engine is not None else None
        self._acc_flat = _fold_compressed_into(
            acc, update, w, self._padded_len, self._use_pallas(), interp
        )
        if block:
            jax.block_until_ready(self._acc_flat)
        self._wsum += w
        self.n_clients += 1
        if self._engine is not None:
            wire = wire_bytes if wire_bytes is not None else update.wire_bytes
            self._engine.stats.record(update.dense_bytes, wire)

    def _use_pallas(self) -> bool:
        if self._engine is not None:
            return bool(self._engine.use_pallas)
        return jax.default_backend() == "tpu"

    def add_stale(
        self,
        params: Any,
        weight: float,
        stale_rounds: int,
        discount: float,
        block: bool = False,
        client_id: Optional[str] = None,
    ) -> float:
        """Fold a carried-over (stale) update with a staleness-discounted
        weight ``weight * discount**stale_rounds``; returns the effective
        weight that entered the average."""
        if stale_rounds < 1:
            raise ValueError("a stale fold must be at least one round late")
        if not 0.0 <= discount <= 1.0:
            raise ValueError("staleness discount must be in [0, 1]")
        w_eff = float(weight) * float(discount) ** int(stale_rounds)
        self.add(params, w_eff, block=block, client_id=client_id)
        return w_eff

    def fold_carry(
        self,
        buffer: CarryOverBuffer,
        round_idx: int,
        discount: float,
        block: bool = False,
    ) -> List[Tuple[CarryEntry, float]]:
        """Drain a :class:`CarryOverBuffer` into the accumulator.

        Every parked entry is folded with its staleness discount applied
        (age = ``round_idx - origin_round`` rounds, at least 1); returns
        the ``(entry, effective_weight)`` pairs so callers can account the
        raw-vs-discounted weights (weight conservation audits)."""
        folded: List[Tuple[CarryEntry, float]] = []
        for entry in buffer.drain():
            w_eff = self.add_stale(
                entry.params, entry.weight, entry.age_at(round_idx),
                discount, block=block, client_id=entry.client_id,
            )
            folded.append((entry, w_eff))
        return folded

    # -- hierarchy: partial-sum export / fold -------------------------------
    def export_partial(self, region_id: str = "") -> PartialSum:
        """Consume the fold as a :class:`PartialSum` instead of params.

        The regional half of the hierarchy: the padded accumulator,
        weight total, and client count leave as one composable unit (the
        base is NOT applied — the parent holds the same base and applies
        it once at finalize).  Flat/delta mode only: partial sums
        compose only against a shared base.  Like :meth:`result`, the
        per-fold state is consumed."""
        if self._plan is None or self._base_flat is None:
            raise ValueError(
                "export_partial() requires flat/delta mode: partial sums "
                "compose only against a shared base — construct the "
                "aggregator with streaming(base=global_params)"
            )
        if self.n_clients == 0:
            raise ValueError("no clients have been added")
        partial = PartialSum(
            acc=self._ensure_flat_acc(),
            wsum=self._wsum,
            n_clients=self.n_clients,
            plan_signature=self._plan.signature,
            base_round=self.base_round,
            region_id=region_id,
        )
        self._reset()
        if self._engine is not None:
            self._engine.stats.n_calls += 1
        return partial

    def fold_partial(self, partial: PartialSum, block: bool = False) -> None:
        """Fold a regional :class:`PartialSum` into this accumulator.

        One donated elementwise add over the padded fp32 buffers —
        weighted partial sums compose associatively, so a parent folding
        R regional partials computes exactly the flat engine's
        ``sum_i w_i * (update_i - base)`` over all N clients.  The
        partial's plan signature and base-round tag must match this
        aggregator's (folding a partial taken against a different
        structure or base is silent corruption)."""
        if self._plan is None or self._base_flat is None:
            raise ValueError(
                "fold_partial() requires flat/delta mode: construct the "
                "aggregator with streaming(base=global_params)"
            )
        if partial.n_clients < 1:
            raise ValueError("a partial sum must carry at least one client")
        if partial.wsum < 0:
            raise ValueError("partial weight total must be non-negative")
        if partial.plan_signature != self._plan.signature:
            raise StructureMismatchError(
                f"partial sum from region {partial.region_id!r} was taken "
                f"against plan {partial.plan_signature}, but this "
                f"aggregator's plan is {self._plan.signature}",
                client_id=partial.region_id or None,
            )
        if partial.base_round != self.base_round:
            raise ValueError(
                f"partial sum from region {partial.region_id!r} was "
                f"accumulated against base round {partial.base_round}, but "
                f"the aggregator's base is round {self.base_round}"
            )
        other = jnp.asarray(partial.acc, jnp.float32)
        acc = self._ensure_flat_acc()
        if other.shape != acc.shape:
            raise ValueError(
                f"partial accumulator has shape {other.shape}; the parent's "
                f"padded accumulator is {acc.shape}"
            )
        self._acc_flat = _flat_partial_fold(acc, other)
        if block:
            jax.block_until_ready(self._acc_flat)
        self._wsum += float(partial.wsum)
        self.n_clients += int(partial.n_clients)
        if self._engine is not None:
            nbytes = _leaf_nbytes(other)
            self._engine.stats.record(nbytes, nbytes)

    def result(self) -> Any:
        if self._acc is None and self._acc_flat is None:
            raise ValueError("no clients have been added")
        if self._wsum <= 0:
            raise ValueError("aggregation weights must sum to a positive value")
        if self._acc_flat is not None:
            assert self._plan is not None and self._base_flat is not None
            vec = _flat_finalize(
                self._acc_flat, self._base_flat, jnp.float32(1.0 / self._wsum)
            )
            out = self._plan.unflatten(vec)
        else:
            acc = _scale_acc(self._acc, jnp.float32(1.0 / self._wsum))
            leaves = jax.tree.leaves(acc)
            assert self._dtypes is not None
            outs = [l.astype(dt) for l, dt in zip(leaves, self._dtypes)]
            out = jax.tree.unflatten(self._treedef, outs)
        # Consume: the accumulator was donated, and every per-fold field
        # (_wsum, n_clients, _dtypes, _treedef) must go with it — stale
        # normalizer state would silently double-count on reuse.
        self._reset()
        if self._engine is not None:
            self._engine.stats.n_calls += 1
        return out


class StructuredStreamingAggregator:
    """Per-group streaming folds under an :class:`UpdateSchema`.

    Each named group keeps its own padded fp32 delta accumulator and its
    own running weight total, so silos may ship any subset of the groups
    — a silo absent from a group contributes no weight to it, and each
    element of the finalized model normalizes by the weight total of the
    groups that actually cover it (overlapping groups sum their
    totals).  Elements no present group covers keep the base exactly.

    ``add`` accepts three payload shapes per client:

    * a :class:`~repro.federated.compression.StructuredUpdate` (the wire
      form) — per-group raw fp32 *values* or per-group compressed
      *deltas* against the aggregator's base;
    * a plain mapping ``{group name: payload}`` with the same per-group
      semantics (a compact fp32 vector is the group's raw values, a
      ``CompressedUpdate`` a delta);
    * a full model pytree — structure-validated, then sliced into every
      group (the dense degenerate case).

    A full-coverage schema (every leaf in exactly one group) with every
    client present in every group folds *bit-for-bit* identically to the
    dense flat/delta path: the per-group folds run the same jitted ops
    over the same values in the same order, the per-element numerator is
    placed by an exact scatter into zeros, and the per-leaf normalizer
    rounds ``1/wsum`` exactly as the dense finalize does.
    """

    def __init__(
        self,
        engine: Optional[AggregationEngine],
        schema: Union[UpdateSchema, ResolvedSchema, Mapping[str, Any]],
        base: Any,
        base_round: Optional[int] = None,
    ) -> None:
        if base is None:
            raise ValueError(
                "structured aggregation needs the round's global weights: "
                "pass base= (per-group deltas and absent groups are both "
                "defined relative to it)"
            )
        self._engine = engine
        if isinstance(schema, ResolvedSchema):
            self._schema = schema
        else:
            self._schema = as_update_schema(
                cast(Union[UpdateSchema, Mapping[str, Any]], schema)
            ).resolve(base)  # type: ignore[union-attr]
        self._plan = self._schema.plan
        self._base_flat = self._plan.flatten(base)
        self._group_base: Dict[str, Any] = {
            name: gp.flatten(base) for name, gp in self._schema.groups
        }
        self.base_round = base_round
        self._accs: Dict[str, Any] = {}
        self._wsums: Dict[str, float] = {n: 0.0 for n in self._schema.group_names}
        self._counts: Dict[str, int] = {n: 0 for n in self._schema.group_names}
        self.n_clients = 0

    @property
    def schema(self) -> ResolvedSchema:
        return self._schema

    @property
    def mid_fold(self) -> bool:
        return self.n_clients > 0 or bool(self._accs)

    def group_wsums(self) -> Dict[str, float]:
        """Per-group running weight totals (weight-conservation audits)."""
        return dict(self._wsums)

    def group_counts(self) -> Dict[str, int]:
        """Per-group client counts (a silo counts once per group present)."""
        return dict(self._counts)

    def _reset(self) -> None:
        self._accs = {}
        self._wsums = {n: 0.0 for n in self._schema.group_names}
        self._counts = {n: 0 for n in self._schema.group_names}
        self.n_clients = 0

    def rebase(self, base: Any, base_round: Optional[int] = None) -> None:
        """Re-anchor on a new round's global weights (see
        :meth:`StreamingAggregator.rebase` for why mid-fold is rejected)."""
        if self.mid_fold:
            raise ValueError(
                "cannot rebase mid-fold: the accumulators hold deltas "
                "against the current base — call result() (or "
                "export_partial()) first"
            )
        plan = plan_for(base)
        if plan.signature != self._plan.signature:
            mismatch = _first_structure_mismatch(
                self._plan.treedef, self._plan.shapes, base
            )
            raise StructureMismatchError(
                "rebase() base does not match the aggregator's plan"
                + (f" at leaf {mismatch[0]!r}: {mismatch[1]}" if mismatch else ""),
                path=mismatch[0] if mismatch else None,
            )
        self._base_flat = plan.flatten(base)
        self._group_base = {
            name: gp.flatten(base) for name, gp in self._schema.groups
        }
        self.base_round = base_round

    def _ensure_acc(self, name: str) -> Any:
        acc = self._accs.get(name)
        if acc is None:
            acc = jnp.zeros(self._schema.group(name).padded_len, jnp.float32)
            self._accs[name] = acc
        return acc

    def _check_base_round(
        self, update_round: Optional[int], client_id: Optional[str]
    ) -> None:
        if update_round is not None and update_round != self.base_round:
            who = f" from client {client_id!r}" if client_id is not None else ""
            raise ValueError(
                f"structured update{who} was encoded against base round "
                f"{update_round}, but the aggregator's base is "
                f"{'untagged' if self.base_round is None else f'round {self.base_round}'}"
                " — rebase(new_base, base_round=...) the aggregator onto "
                "the update's round before folding"
            )

    def _payload_items(
        self, params: Any, client_id: Optional[str]
    ) -> Tuple[List[Tuple[str, Any]], Optional[int]]:
        """Normalize one client's payload to [(group, payload)] + wire bytes."""
        from repro.federated.compression import CompressedUpdate, StructuredUpdate
        if isinstance(params, StructuredUpdate):
            if params.schema_signature != self._schema.signature:
                who = (f" from client {client_id!r}"
                       if client_id is not None else "")
                raise ValueError(
                    f"structured update{who} was encoded under schema "
                    f"{params.schema_signature}, but the aggregator's "
                    f"schema is {self._schema.signature}"
                )
            self._check_base_round(params.base_round, client_id)
            return list(params.groups), params.wire_bytes
        # A plain mapping is a {group: payload} dict only when its keys are
        # all schema group names and its values are wire payloads (compact
        # vectors / CompressedUpdates) — a model pytree whose top level is
        # a dict of sub-trees falls through to the full-tree branch.
        if isinstance(params, Mapping) and params and all(
            k in self._wsums
            and not isinstance(v, Mapping)
            and (isinstance(v, CompressedUpdate)
                 or np.ndim(v) == 1)
            for k, v in params.items()
        ):
            return list(params.items()), None
        # A full model pytree: validate structure, slice every group out.
        mismatch = _first_structure_mismatch(
            self._plan.treedef, self._plan.shapes, params
        )
        if mismatch is not None:
            _raise_structure_mismatch(mismatch, client_id)
        return (
            [(name, gp.flatten(params)) for name, gp in self._schema.groups],
            None,
        )

    def add(
        self,
        params: Any,
        weight: float,
        block: bool = False,
        wire_bytes: Optional[int] = None,
        client_id: Optional[str] = None,
    ) -> None:
        """Fold one client's (possibly partial) structured update in.

        ``weight`` applies to every group the client shipped; groups the
        client omitted see neither the update nor the weight."""
        from repro.federated.compression import CompressedUpdate
        w = float(weight)
        if w < 0:
            raise ValueError("client weight must be non-negative")
        items, payload_wire = self._payload_items(params, client_id)
        if not items:
            raise ValueError("a structured update must carry at least one group")
        folded_bytes = 0
        last: Any = None
        for name, payload in items:
            if name not in self._wsums:
                raise ValueError(
                    f"update carries unknown group {name!r}; the schema's "
                    f"groups are {list(self._schema.group_names)}"
                )
            gp = self._schema.group(name)
            acc = self._ensure_acc(name)
            if isinstance(payload, CompressedUpdate):
                self._check_base_round(payload.base_round, client_id)
                if payload.total_elems != gp.total_elems:
                    raise ValueError(
                        f"group {name!r} update has {payload.total_elems} "
                        f"elements; the group has {gp.total_elems}"
                    )
                interp = (self._engine.interpret
                          if self._engine is not None else None)
                self._accs[name] = _fold_compressed_into(
                    acc, payload, w, gp.padded_len, self._use_pallas(), interp
                )
                folded_bytes += payload.dense_bytes
            else:
                vec = jnp.asarray(payload, jnp.float32).reshape(-1)
                if vec.shape[0] != gp.total_elems:
                    raise ValueError(
                        f"group {name!r} payload has {vec.shape[0]} "
                        f"elements; the group has {gp.total_elems}"
                    )
                self._accs[name] = _flat_delta_fold(
                    acc, vec, self._group_base[name], jnp.float32(w)
                )
                folded_bytes += gp.total_elems * 4
            last = self._accs[name]
            self._wsums[name] += w
            self._counts[name] += 1
        if block and last is not None:
            jax.block_until_ready(last)
        self.n_clients += 1
        if self._engine is not None:
            wire = wire_bytes if wire_bytes is not None else payload_wire
            self._engine.stats.record(folded_bytes, wire)

    def add_stale(
        self,
        params: Any,
        weight: float,
        stale_rounds: int,
        discount: float,
        block: bool = False,
        client_id: Optional[str] = None,
    ) -> float:
        """Staleness-discounted structured fold (mirrors the dense rule)."""
        if stale_rounds < 1:
            raise ValueError("a stale fold must be at least one round late")
        if not 0.0 <= discount <= 1.0:
            raise ValueError("staleness discount must be in [0, 1]")
        w_eff = float(weight) * float(discount) ** int(stale_rounds)
        self.add(params, w_eff, block=block, client_id=client_id)
        return w_eff

    def fold_carry(
        self,
        buffer: CarryOverBuffer,
        round_idx: int,
        discount: float,
        block: bool = False,
    ) -> List[Tuple[CarryEntry, float]]:
        """Drain parked entries with the age discount (dense parity)."""
        folded: List[Tuple[CarryEntry, float]] = []
        for entry in buffer.drain():
            w_eff = self.add_stale(
                entry.params, entry.weight, entry.age_at(round_idx),
                discount, block=block, client_id=entry.client_id,
            )
            folded.append((entry, w_eff))
        return folded

    def _use_pallas(self) -> bool:
        if self._engine is not None:
            return bool(self._engine.use_pallas)
        return jax.default_backend() == "tpu"

    # -- hierarchy: per-group partial export / fold --------------------------
    def export_partial(self, region_id: str = "") -> StructuredPartialSum:
        """Consume the fold as one :class:`PartialSum` per present group.

        Groups no client contributed to are omitted entirely — absent
        silos contribute no weight, and the parent must see that."""
        if self.n_clients == 0:
            raise ValueError("no clients have been added")
        groups: List[Tuple[str, PartialSum]] = []
        for name, gp in self._schema.groups:
            if self._counts[name] == 0:
                continue
            groups.append((name, PartialSum(
                acc=self._ensure_acc(name),
                wsum=self._wsums[name],
                n_clients=self._counts[name],
                plan_signature=gp.signature,
                base_round=self.base_round,
                region_id=region_id,
            )))
        partial = StructuredPartialSum(
            groups=tuple(groups),
            schema_signature=self._schema.signature,
            n_clients=self.n_clients,
            base_round=self.base_round,
            region_id=region_id,
        )
        self._reset()
        if self._engine is not None:
            self._engine.stats.n_calls += 1
        return partial

    def fold_partial(
        self, partial: StructuredPartialSum, block: bool = False
    ) -> None:
        """Fold a regional :class:`StructuredPartialSum` in, per group."""
        if partial.schema_signature != self._schema.signature:
            raise StructureMismatchError(
                f"structured partial from region {partial.region_id!r} was "
                f"taken under schema {partial.schema_signature}, but this "
                f"aggregator's schema is {self._schema.signature}",
                client_id=partial.region_id or None,
            )
        if partial.base_round != self.base_round:
            raise ValueError(
                f"structured partial from region {partial.region_id!r} was "
                f"accumulated against base round {partial.base_round}, but "
                f"the aggregator's base is round {self.base_round}"
            )
        if partial.n_clients < 1:
            raise ValueError("a partial sum must carry at least one client")
        last: Any = None
        total_bytes = 0
        for name, p in partial.groups:
            if name not in self._wsums:
                raise ValueError(
                    f"structured partial carries unknown group {name!r}"
                )
            gp = self._schema.group(name)
            if p.plan_signature != gp.signature:
                raise StructureMismatchError(
                    f"group {name!r} partial was taken against plan "
                    f"{p.plan_signature}, but this aggregator's group plan "
                    f"is {gp.signature}",
                    client_id=partial.region_id or None,
                )
            if p.wsum < 0:
                raise ValueError("partial weight total must be non-negative")
            other = jnp.asarray(p.acc, jnp.float32)
            acc = self._ensure_acc(name)
            if other.shape != acc.shape:
                raise ValueError(
                    f"group {name!r} partial accumulator has shape "
                    f"{other.shape}; the parent's is {acc.shape}"
                )
            self._accs[name] = _flat_partial_fold(acc, other)
            last = self._accs[name]
            self._wsums[name] += float(p.wsum)
            self._counts[name] += int(p.n_clients)
            total_bytes += _leaf_nbytes(other)
        if block and last is not None:
            jax.block_until_ready(last)
        self.n_clients += int(partial.n_clients)
        if self._engine is not None:
            self._engine.stats.record(total_bytes, total_bytes)

    def result(self) -> Any:
        """Finalize: scatter per-group numerators into one model-sized
        vector, normalize each element by its covering groups' weight
        total, and read out ``base + numerator / wsum`` per element."""
        if self.n_clients == 0:
            raise ValueError("no clients have been added")
        if not any(w > 0 for w in self._wsums.values()):
            raise ValueError("aggregation weights must sum to a positive value")
        num = jnp.zeros(self._plan.total_elems, jnp.float32)
        for name, gp in self._schema.groups:
            if self._counts[name] == 0:
                continue
            acc = self._ensure_acc(name)
            num = _flat_group_scatter(
                num, jnp.asarray(gp.offsets), acc[: gp.total_elems]
            )
        # Per-element normalizer, built host-side from the per-leaf
        # coverage map: each leaf's denominator is the sum (schema
        # order, Python-float accumulation — the dense path's exact
        # arithmetic) of its covering groups' weight totals, skipping
        # groups nobody shipped.  Zero-weight elements keep the base.
        inv_np = np.zeros(self._plan.total_elems, np.float32)
        off = 0
        present_wsums = {
            n: w for n, w in self._wsums.items() if self._counts[n] > 0
        }
        for i, size in enumerate(self._plan.sizes):
            wsum_leaf = 0.0
            for name in self._schema.leaf_groups[i]:
                if name in present_wsums:
                    wsum_leaf += present_wsums[name]
            if wsum_leaf > 0:
                inv_np[off:off + size] = np.float32(1.0 / wsum_leaf)
            off += size
        vec = _flat_finalize_vec(num, self._base_flat, jnp.asarray(inv_np))
        out = self._plan.unflatten(vec)
        self._reset()
        if self._engine is not None:
            self._engine.stats.n_calls += 1
        return out


# ---------------------------------------------------------------------------
# Cost-accounting hook (simulator integration)
# ---------------------------------------------------------------------------

def make_measured_aggreg_fn(
    env: Any,
    bytes_per_round: int,
    gb_per_s: float,
    base_vm_id: Optional[str] = None,
) -> Callable[[str], float]:
    """Build a `CostModel.t_aggreg` override from a measured reduce rate.

    ``bytes_per_round`` is the dense-equivalent byte volume the server
    reduces each round (N clients x model bytes, e.g.
    `AggStats.last_folded_bytes` — the reduce runs over dequantized fp32
    regardless of what crossed the wire, so folded, not wire, bytes set
    the aggregation time);
    ``gb_per_s`` the measured engine bandwidth (benchmarks/aggregation_bench
    reports it per shape).  The time scales with each VM's instance
    slowdown exactly like the paper's `aggreg_bl` baseline does.
    """
    if gb_per_s <= 0:
        raise ValueError("gb_per_s must be positive")
    base_s = bytes_per_round / (gb_per_s * 1e9)
    base_slow = env.inst_slowdown(base_vm_id) if base_vm_id is not None else 1.0

    def t_aggreg(vm_id: str) -> float:
        return base_s * env.inst_slowdown(vm_id) / base_slow

    return t_aggreg
