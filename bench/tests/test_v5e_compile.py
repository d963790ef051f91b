"""The engine's decode and prefill programs of glm4-9b-20l, compiled for
one chip of a described TPU v5e: no chip needed.

The configuration is the benchmark's own file; the programs are the
engine's own jitted bodies, at its 8 slots x 8192 tokens. Arguments
plus temporaries must fit the chip's HBM, so a cell that would run out
of memory fails here first. The topology is described inside a fixture,
never at import: only one process at a time may load the TPU library.
"""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import spec, weights

ROOT = pathlib.Path(__file__).resolve().parents[2]
HBM = 15.75 * 2 ** 30          # what a v5e chip gives a program


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def glm(one_chip):
    """(engine built at the cell's sizes, shapes on the chip, mesh)."""
    from repro.configs.base import SHAPES, MeshConfig, RunConfig
    from repro.serving.engine import ServingEngine

    config = json.loads((ROOT / "bench/configs/glm4-9b-20l.json").read_text())
    cfg = spec.model_config(config)
    rc = RunConfig(model=cfg, shape=SHAPES["decode_32k"], mesh=MeshConfig())
    pshape = jax.eval_shape(lambda: weights.program_params(0, config["model"]))
    engine = ServingEngine(pshape, cfg, rc,
                           config=spec.serve_config(config, 0))
    rep = NamedSharding(one_chip, P())

    def on(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
            jax.eval_shape(lambda: tree))

    return engine, on, one_chip


def _fits(compiled):
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM, mem
    return used


def test_glm4_20l_decode_fits_one_chip(glm):
    engine, on, mesh = glm
    with jax.set_mesh(mesh):
        compiled = jax.jit(engine._decode_sample, donate_argnums=(1,)).lower(
            on(engine.params), on(engine.cache), on(engine.last_tokens),
            on(engine.key)).compile()
    _fits(compiled)


@pytest.mark.parametrize("sample", [False, True])
def test_glm4_20l_prefill_chunk_fits_one_chip(glm, sample):
    engine, on, mesh = glm
    i32 = jnp.zeros((), jnp.int32)
    toks = jnp.zeros((1, engine.prefill_chunk), jnp.int32)
    with jax.set_mesh(mesh):
        compiled = jax.jit(engine._prefill_chunk_body, donate_argnums=(1,),
                           static_argnums=(8,)).lower(
            on(engine.params), on(engine.cache), on(toks), on(i32), on(i32),
            on(i32), on(engine.last_tokens), on(engine.key),
            sample).compile()
    _fits(compiled)
