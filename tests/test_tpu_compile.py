"""Compile the main-path Pallas kernels for a TPU v5e at real sizes.

Nothing runs: each case lowers a kernel against a described (not
attached) v5e chip and asks the TPU compiler to build it, which catches
what interpret mode cannot — block shapes Mosaic refuses, vector types
it cannot load, more VMEM than a kernel may use.  Sizes are the paper's
TIL deployment: four silos folding VGG16 updates of 134,268,738
parameters.

The topology is described inside a module fixture (never at import),
so every test worker collects the same tests and only the worker that
runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fedavg_reduce import BLOCK, dequant_fold, fedavg_reduce

TIL_PARAMS = 134_268_738
TIL_PADDED = -(-TIL_PARAMS // BLOCK) * BLOCK


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """Described-chip compiles cannot be read back from the persistent
    cache; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, *shapes):
    return fn.lower(*shapes, interpret=False).compile().as_text()


def test_fedavg_reduce_compiles_for_v5e_at_til_size(one_chip):
    text = _compiled_text(
        fedavg_reduce,
        jax.ShapeDtypeStruct((4, TIL_PARAMS), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((4,), jnp.float32, sharding=one_chip),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.float16])
def test_dequant_fold_compiles_for_v5e_at_til_size(one_chip, dtype):
    text = _compiled_text(
        dequant_fold,
        jax.ShapeDtypeStruct((TIL_PADDED,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((TIL_PADDED,), dtype, sharding=one_chip),
        jax.ShapeDtypeStruct((TIL_PADDED // BLOCK,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip),
    )
    assert "tpu_custom_call" in text


def test_ravel_plan_unflatten_fits_v5e_at_til_size(one_chip):
    """The flat fold's unflatten must stay a set of slices: left to
    itself the TPU compiler reshaped the whole VGG16 vector to
    (L/2, 2), which pads 64x to 34 GB of HBM."""
    from repro.federated.agg_engine import plan_for
    from repro.models.fl_models import VGGConfig, init_vgg16

    shapes = jax.eval_shape(
        lambda: init_vgg16(jax.random.PRNGKey(0), VGGConfig(image_size=224))
    )
    plan = plan_for(shapes)
    assert plan.total_elems == TIL_PARAMS
    compiled = plan.unflatten.lower(
        jax.ShapeDtypeStruct((TIL_PARAMS,), jnp.float32, sharding=one_chip)
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * TIL_PARAMS
