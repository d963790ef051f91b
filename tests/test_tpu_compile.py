"""Compile rehearsal for a described TPU v5e chip: no chip needed.

The TPU compiler refuses what the CPU interpreter accepts: block shapes off
the (8, 128) tiling, kernels over their fast-memory budget, programs that
do not fit the chip's HBM. Each test here compiles a main-path program at
published widths for one chip of a described ``v5e:2x2`` topology, so such
a fault fails here instead of on the chip.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and it holds it until exit.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import registry
from repro.configs.base import HBM_PER_CHIP, MeshConfig, RunConfig, SHAPES
from repro.kernels import widths
from repro.models import model as M
from repro.parallel import sharding as shlib
from repro.serving.engine import layer_scan_unroll

GiB = 1 << 30


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 topology, with the compilation cache off.

    The cache is off because a TPU executable written to it cannot be read
    back without a chip: a later compile would warn and compile again.
    """
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))


def _on(mesh, tree):
    """ShapeDtypeStructs of ``tree``, replicated over ``mesh``."""
    rep = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep), tree)


@pytest.mark.parametrize("case", widths.CASES, ids=lambda c: c.name)
def test_kernel_compiles_for_v5e(one_chip, case):
    args = _on(one_chip, jax.eval_shape(case.make_args,
                                        jax.random.PRNGKey(0)))
    compiled = jax.jit(
        lambda *a: case.kernel(*a, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen3_decode_step_fits_one_chip(one_chip):
    """The engine's decode tick at chip_smoke's 8 slots x 4096 tokens.

    Arguments plus temp must fit the chip's HBM, and the temp must stay
    under one copy of the cache plus 1 GiB: a layer-scan unroll that does
    not divide the layer count, or a deep unroll of a large cache, adds
    GiBs of cache-slice copies.
    """
    n_slots, max_seq = 8, 4096
    cfg = registry.get("qwen3-1.7b")
    rc = RunConfig(model=cfg, shape=SHAPES["decode_32k"], mesh=MeshConfig())
    hot = dataclasses.replace(
        rc, sr_prefetch_depth=0,
        scan_unroll=layer_scan_unroll(cfg, rc, n_slots, max_seq))
    pshape = jax.eval_shape(lambda: M.init_model(jax.random.PRNGKey(0), cfg))
    pspecs = shlib.param_specs(pshape, tier=rc.param_tier)
    cshape = M.cache_init(cfg, rc, n_slots, max_seq=max_seq, as_shape=True)
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(cshape))

    def tick(params, cache, last_tokens):
        logits, cache = M.decode_step(params, cfg, hot, last_tokens[:, None],
                                      cache, pspecs)
        return cache, M.sample_tokens(M.last_token_logits(logits), None, 0.0)

    with jax.set_mesh(one_chip):
        compiled = jax.jit(tick, donate_argnums=(1,)).lower(
            _on(one_chip, pshape), _on(one_chip, cshape),
            _on(one_chip, jax.ShapeDtypeStruct((n_slots,), jnp.int32))
        ).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < cache_bytes + GiB, mem
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < HBM_PER_CHIP, mem


def test_qwen3_moe_share_programs_fit_one_chip(one_chip):
    """The engine's decode tick and prefill chunk of qwen3-moe-235b-a22b
    at one chip's share (16 of 94 layers, 8 of 128 experts) and 8 slots x
    8192 tokens: 10.97 GiB of arguments, and the decode tick's temp is the
    second cache copy (2 GiB) and the expert layer's buffers."""
    from repro.serving.engine import ServingEngine
    cfg = dataclasses.replace(registry.get("qwen3-moe-235b-a22b"),
                              n_layers=16, n_experts_held=8)
    rc = RunConfig(model=cfg, shape=SHAPES["decode_32k"], mesh=MeshConfig())
    pshape = jax.eval_shape(lambda: M.init_model(jax.random.PRNGKey(0), cfg))
    eng = ServingEngine(pshape, cfg, rc, n_slots=8, max_seq=8192,
                        prefill_chunk=128)
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(eng.cache))
    i32 = jnp.zeros((), jnp.int32)
    toks = jnp.zeros((1, 128), jnp.int32)

    def on(tree):
        return _on(one_chip, jax.eval_shape(lambda: tree))

    with jax.set_mesh(one_chip):
        decode = jax.jit(eng._decode_sample, donate_argnums=(1,)).lower(
            on(eng.params), on(eng.cache), on(eng.last_tokens),
            on(eng.key)).compile()
        prefill = jax.jit(eng._prefill_chunk_body, donate_argnums=(1,),
                          static_argnums=(8,)).lower(
            on(eng.params), on(eng.cache), on(toks), on(i32), on(i32),
            on(i32), on(eng.last_tokens), on(eng.key), True).compile()
    for compiled in (decode, prefill):
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < cache_bytes + GiB, mem
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            < HBM_PER_CHIP, mem
