"""End-to-end system tests: serving engine, sharding rules, small dry-run.

The distributed-equivalence test (paged decode on a real 2x4 device mesh
vs single device) runs in a subprocess because the forced device count
must be set before the first jax import.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import registry
from repro.configs.base import MeshConfig, RunConfig, SHAPES
from repro.launch import steps as steps_lib
from repro.models import model as M
from repro.parallel import sharding as shlib
from repro.serving.engine import Request, ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------ serving

def test_serving_engine_completes_requests(mesh_ctx):
    cfg = registry.smoke("qwen3-1.7b")
    rc = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                   mesh=MeshConfig())
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, rc, n_slots=2, max_seq=32)
    for rid in range(4):
        eng.submit(Request(rid=rid, prompt=[1 + rid, 2, 3],
                           max_new_tokens=4))
    done = eng.run(max_ticks=200)
    assert len(done) == 4
    assert all(len(r.generated) == 4 for r in done)
    assert len(eng.store.pages) == 4       # retired pages reached the tier


def test_serving_batching_matches_solo(mesh_ctx):
    """Continuous batching must not change a request's tokens vs running
    it alone (slot isolation, greedy sampling)."""
    cfg = registry.smoke("qwen3-1.7b")
    rc = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                   mesh=MeshConfig())
    params = M.init_model(jax.random.PRNGKey(0), cfg)

    solo = ServingEngine(params, cfg, rc, n_slots=1, max_seq=32)
    solo.submit(Request(rid=0, prompt=[5, 6, 7], max_new_tokens=5))
    ref = solo.run(max_ticks=100)[0].generated

    batched = ServingEngine(params, cfg, rc, n_slots=3, max_seq=32)
    batched.submit(Request(rid=0, prompt=[5, 6, 7], max_new_tokens=5))
    batched.submit(Request(rid=1, prompt=[9, 9], max_new_tokens=3))
    batched.submit(Request(rid=2, prompt=[1], max_new_tokens=6))
    outs = {r.rid: r.generated for r in batched.run(max_ticks=200)}
    assert outs[0] == ref


# ----------------------------------------------------------------- sharding

def test_param_specs_rules():
    shapes = {
        "blocks": {"attn": {"wq": jax.ShapeDtypeStruct((4, 64, 128),
                                                        jnp.bfloat16)},
                   "mlp": {"w_down": jax.ShapeDtypeStruct((4, 256, 64),
                                                          jnp.bfloat16)}},
        "embed": {"embedding": jax.ShapeDtypeStruct((1600, 64),
                                                    jnp.bfloat16)},
    }
    specs = shlib.param_specs(shapes, tier="pool")
    assert specs["blocks"]["attn"]["wq"] == P(None, "data", "model")
    assert specs["blocks"]["mlp"]["w_down"] == P(None, "model", "data")
    assert specs["embed"]["embedding"] == P("model", "data")
    # device tier strips the FSDP axis
    dev = shlib.param_specs(shapes, tier="device")
    assert dev["blocks"]["attn"]["wq"] == P(None, None, "model")


def test_divisibility_guard():
    shapes = {"blocks": {"attn": {"wq": jax.ShapeDtypeStruct(
        (4, 60, 100), jnp.bfloat16)}}}    # 60 % 16 != 0, 100 % 16 != 0
    specs = shlib.param_specs(shapes, tier="pool")
    assert specs["blocks"]["attn"]["wq"] == P(None, None, None)


def test_gathered_specs_strips_fsdp():
    specs = {"w": P("data", "model"), "b": P(("pod", "data"),)}
    g = shlib.gathered_specs(specs)
    assert g["w"] == P(None, "model")
    assert g["b"] == P(None)


# ------------------------------------------------------- small-mesh dry-run

@pytest.mark.parametrize("shape_name", ["train_4k", "prefill_32k",
                                        "decode_32k"])
def test_assemble_compiles_on_host_mesh(mesh_ctx, shape_name):
    """steps.assemble lower+compile on the 1x1 mesh with a reduced shape
    — the same path the 512-device dry-run exercises."""
    import dataclasses
    cfg = registry.smoke("qwen3-1.7b")
    shape = dataclasses.replace(SHAPES[shape_name], global_batch=2,
                                seq_len=64)
    rc = RunConfig(model=cfg, shape=shape, mesh=MeshConfig())
    cell = steps_lib.assemble(cfg, shape, rc, mesh_ctx)
    compiled = cell.jitted.lower(*cell.args).compile()
    assert compiled.cost_analysis() is not None


# --------------------------------------------------- distributed (8 device)

_DISTRIBUTED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np, json
    from jax.sharding import AxisType
    from repro.configs import registry
    from repro.configs.base import MeshConfig, RunConfig, SHAPES
    from repro.models import model as M
    from repro.parallel import sharding as shlib

    cfg = registry.smoke("qwen3-1.7b")
    rc = RunConfig(model=cfg, shape=SHAPES["decode_32k"], mesh=MeshConfig())
    mesh8 = jax.make_mesh((2, 4), ("data", "model"),
                          axis_types=(AxisType.Auto,) * 2)
    mesh1 = jax.make_mesh((1, 1), ("data", "model"),
                          axis_types=(AxisType.Auto,) * 2,
                          devices=jax.devices()[:1])
    outs = {}
    for name, mesh in (("m8", mesh8), ("m1", mesh1)):
        with jax.set_mesh(mesh):
            params = M.init_model(jax.random.PRNGKey(0), cfg)
            specs = shlib.param_specs(jax.eval_shape(lambda: params))
            cache = M.cache_init(cfg, rc, 2, max_seq=64)
            cache["pos"] = jnp.array([3, 1], jnp.int32)
            toks = jnp.array([[5], [7]], jnp.int32)
            logits, cache2 = M.decode_step(params, cfg, rc, toks, cache,
                                           specs)
            outs[name] = np.asarray(logits.astype(jnp.float32))
    np.testing.assert_allclose(outs["m8"], outs["m1"], atol=2e-2, rtol=2e-2)
    print("DISTRIBUTED_OK")
""")


def test_paged_decode_distributed_equivalence():
    """The page-sharded decode on a (2,4) mesh must match 1 device."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _DISTRIBUTED_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert "DISTRIBUTED_OK" in res.stdout, res.stderr[-3000:]
