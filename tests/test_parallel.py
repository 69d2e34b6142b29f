"""Sharding rules, pipeline parallelism, sharded-vs-single equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ASSIGNED, get_config
from repro.models import build_model
from repro.parallel import fit_spec, param_pspec, param_specs
from repro.launch.mesh import make_mesh
from tests._multidevice import run_with_devices


# ------------------------------------------------------------- fit_spec --

def test_fit_spec_basic():
    # single-device mesh: every axis has size 1 → everything fits
    mesh = make_mesh((1, 1), ("data", "model"))
    assert fit_spec(("fsdp", "tp"), (16, 32), mesh) == P("data", "model")
    assert fit_spec(("dp", None), (3, 7), mesh) == P("data", None)


def test_param_specs_always_divisible():
    """Property: for every assigned arch, every arg spec divides its dim
    (jit in_shardings hard requirement) — checked on a fake 16×16 mesh."""
    out = run_with_devices("""
        import jax
        from repro.configs import ASSIGNED, get_config
        from repro.models import build_model, input_specs
        from repro.parallel import param_specs, batch_specs, cache_specs
        from repro.launch.mesh import make_production_mesh
        from repro.launch.mesh import make_mesh

        # 16-device stand-in mesh with the production axis names
        mesh = make_mesh((4, 4), ("data", "model"))

        def check(tree, specs):
            leaves = jax.tree_util.tree_leaves_with_path(tree)
            spec_leaves = jax.tree_util.tree_leaves(
                specs, is_leaf=lambda x: isinstance(x, type(specs)) or True)
            flat_specs = jax.tree_util.tree_leaves(specs)
            for (kp, leaf), spec in zip(leaves, flat_specs):
                for dim, ax in zip(leaf.shape, tuple(spec)):
                    if ax is None: continue
                    axes = (ax,) if isinstance(ax, str) else ax
                    size = 1
                    for a in axes: size *= mesh.shape[a]
                    assert dim % size == 0, (kp, leaf.shape, spec)

        for name in ASSIGNED:
            cfg = get_config(name)
            model = build_model(cfg)
            params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            check(params, param_specs(params, mesh))
            si = input_specs(cfg, "decode", 1024, 16)
            check(si["state"], cache_specs(si["state"], mesh))
        print("OK")
    """, n_devices=16)
    assert "OK" in out


def test_param_pspec_rules():
    mesh = make_mesh((1, 1), ("data", "model"))
    assert param_pspec("trunk/periods/0/attn/wq/w", (4, 64, 64), mesh) \
        == P(None, "data", "model")
    assert param_pspec("embed/tokens", (512, 64), mesh) == P("model", "data")
    assert param_pspec("trunk/periods/0/ln1/scale", (4, 64), mesh) \
        == P(None, None)
    assert param_pspec("trunk/periods/0/moe/up", (4, 8, 64, 128), mesh) \
        == P(None, None, "data", "model")


# ------------------------------------------------------------- pipeline --

def test_pipeline_parallel_matches_sequential():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel import pipeline_apply
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("pod",))
        rng = np.random.default_rng(0)
        S, M, mb, d = 4, 6, 3, 8
        ws = jnp.asarray(rng.normal(size=(S, d, d)).astype(np.float32) * 0.3)
        bs = jnp.asarray(rng.normal(size=(S, d)).astype(np.float32))
        x = jnp.asarray(rng.normal(size=(M, mb, d)).astype(np.float32))
        f = lambda p, h: jnp.tanh(h @ p["w"] + p["b"])
        out = pipeline_apply(f, {"w": ws, "b": bs}, x, mesh, axis="pod")
        ref = x
        for s in range(S):
            ref = jnp.tanh(ref @ ws[s] + bs[s])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)
        print("OK")
    """, n_devices=4)
    assert "OK" in out


# ------------------------------------------- sharded == single device --

def test_sharded_train_step_matches_single():
    """The same loss on a 2×4 mesh and on CPU-1 — distribution must not
    change the math."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.models import build_model
        from repro.parallel import (param_specs, batch_specs, shard_tree,
                                    activation_sharding)
        from repro.launch.mesh import make_mesh

        cfg = get_config("deepseek-7b-smoke")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32)}
        batch["labels"] = batch["tokens"]
        loss_single, _ = model.loss(params, batch)

        mesh = make_mesh((2, 4), ("data", "model"))
        pspecs = param_specs(params, mesh)
        sparams = shard_tree(params, pspecs, mesh)
        bspecs = batch_specs(batch, mesh)
        sbatch = {k: jax.device_put(v, NamedSharding(mesh, bspecs[k]))
                  for k, v in batch.items()}
        with activation_sharding(mesh):
            loss_sharded, _ = jax.jit(model.loss)(sparams, sbatch)
        d = abs(float(loss_single) - float(loss_sharded))
        assert d < 5e-3, d
        print("OK", d)
    """, n_devices=8)
    assert "OK" in out
