#!/usr/bin/env python3
"""Readings that a cell's limits are set from, without the program.

For each seed it makes the cell's weights and silos, runs the plain
reference round at the precision the configuration states (float32 at
JAX's default matmul precision) and, in the program's place:

* ``highest``: the same reference at float32 ``highest`` matmul
  precision, which shows how far the stated precision alone moves the
  numbers;
* ``control``: the reference in bfloat16, the precision below the
  configuration's float32: it has to come out not correct;
* ``half``: the fold over the first half of the silos only, the mean
  taken over the rest;
* ``half_batch``: every silo's step sees the first half of each batch,
  the mean taken over the rest;
* ``altered``: one silo's update changed where it is produced (its delta
  doubled).

For ``half_batch``, ``highest`` and ``control`` it also compares their
first local steps of every silo with the reference's.  A step that
returns the weights unchanged reads 1 on ``change_gap`` and needs no
run.  Prints one JSON line per seed and reading.

Sound runs of the program are read from ``bench/run.py``, which prints
its numbers on the ``reference_numbers`` line.

Usage: python bench/calibrate.py --workload til_dense --seeds 11 12 13
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional, Sequence

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import common  # noqa: E402


def _half_batch_reference(cfg: Dict[str, Any], module: Any) -> Any:
    """The reference whose step sees the first half of each batch."""
    from bench import fl_reference as flr

    class HalfBatch(flr.Reference):
        def _batch(self, b: Any) -> Any:
            x, y = super()._batch(b)
            n = max(1, len(y) // 2)
            return x[:n], y[:n]
    return HalfBatch(cfg, module)


def readings(cfg: Dict[str, Any], module: Any, codec: Optional[str], seed: int,
             kinds: Sequence[str]) -> Dict[str, Dict[str, Any]]:
    import jax
    import jax.numpy as jnp

    from bench import fl_reference as flr

    key = common.seed_key(seed)
    k_params, k_data = jax.random.split(key)
    params0 = jax.jit(lambda k: module.init_params(cfg, k))(k_params)
    silos = module.make_silos(cfg, seed, k_data)
    weights = [float(len(s["train"][1])) for s in silos]
    ref = flr.Reference(cfg, module)
    ref_steps = [ref.first_steps(params0, s["train"]) for s in silos]
    grads = ref_steps[0]["grad"]
    ups = flr.silo_updates(ref, params0, silos, codec)
    r_new = flr.fedavg(ups, weights)
    r_loss = ref.eval_loss(r_new, silos)
    base, r_leaves = flr.host_leaves(params0), flr.host_leaves(r_new)

    def versus(new: Any, other: Optional[Any] = None) -> Dict[str, Any]:
        """The fold's numbers; with ``other`` in the program's place
        from the first step, its first steps' numbers too."""
        loss = ref.eval_loss(new, silos)
        out = flr.compare(base, flr.host_leaves(new), r_leaves, loss, r_loss, grads)
        if other is not None:
            steps = [other.first_steps(params0, s["train"]) for s in silos]
            out = {**flr.compare_steps(steps, ref_steps), **out}
        return out

    out: Dict[str, Dict[str, Any]] = {}
    if "half" in kinds:
        half = max(1, len(silos) // 2)
        out["half"] = versus(flr.fedavg(ups[:half], weights[:half]))
    if "altered" in kinds:
        bad = [jax.tree.map(lambda u, b: b + 2.0 * (u - b), ups[0], params0)] + ups[1:]
        out["altered"] = versus(flr.fedavg(bad, weights))
    del ups
    others = {"half_batch": lambda: _half_batch_reference(cfg, module),
              "highest": lambda: flr.Reference(cfg, module, precision="highest"),
              "control": lambda: flr.Reference(cfg, module, dtype=jnp.bfloat16, precision=None)}
    for kind, make in others.items():
        if kind in kinds:
            other = make()
            new = flr.reference_round(other, params0, silos, codec)["params"]
            out[kind] = versus(new, other)
    return out


def main(argv: Optional[List[str]] = None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--kinds", nargs="+",
                        default=["highest", "control", "half", "half_batch", "altered"])
    args = parser.parse_args(argv)
    common.setup_jax()
    bench = common.benchmark()
    w = common.workload(bench, args.workload)
    common.require_tpu(w["chips"])
    cfg, module = common.config(w["config"])
    codec = common.traffic(w["traffic"]).get("compression")
    for seed in args.seeds:
        for kind, nums in readings(cfg, module, codec, seed, args.kinds).items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "reading": kind, **nums}), flush=True)


if __name__ == "__main__":
    main()
