"""Sharding rules: divisibility-aware fallback, batch/cache specs, and a
small-mesh end-to-end lowering (subprocess, 8 host devices)."""
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_config
from repro.models import abstract_cache, abstract_params
from repro.sharding import (_fits, batch_specs, cache_specs, param_specs,
                            zero_sharded_specs)


class FakeMesh:
    """Mesh stand-in with .shape and .axis_names only (rule fitting)."""

    def __init__(self, **axes):
        self.shape = dict(axes)
        self.axis_names = tuple(axes)


MESH = FakeMesh(data=16, model=16)
MESH_MP = FakeMesh(pod=2, data=16, model=16)


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_param_specs_always_fit(arch):
    cfg = get_config(arch)
    params = abstract_params(cfg)
    specs = param_specs(cfg, params, MESH)

    def check(leaf, spec):
        assert _fits(spec, leaf.shape, MESH), (arch, leaf.shape, spec)

    jax.tree.map(check, params, specs,
                 is_leaf=lambda x: isinstance(x, P))
    # at least half the parameter BYTES must be model-sharded (real TP)
    total = sharded = 0
    for leaf, spec in zip(jax.tree.leaves(params),
                          jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))):
        b = int(np.prod(leaf.shape))
        total += b
        if any(ax is not None for ax in tuple(spec)):
            sharded += b
    assert sharded / total > 0.5, arch


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_zero_specs_fit_and_widen(arch):
    cfg = get_config(arch)
    params = abstract_params(cfg)
    zspecs = zero_sharded_specs(cfg, params, MESH)

    def check(leaf, spec):
        assert _fits(spec, leaf.shape, MESH), (arch, leaf.shape, spec)

    jax.tree.map(check, params, zspecs, is_leaf=lambda x: isinstance(x, P))


def test_yi_padded_heads_shard_cleanly():
    """yi's 56 q-heads are padded to 64 (§Perf H3) so wq/wo shard on the
    head axis; the kv projections (8 heads, non-divisible) replicate."""
    cfg = get_config("yi-34b")
    assert cfg.padded_heads == 64
    params = abstract_params(cfg)
    assert params["layers"]["attn"]["wq"].shape[2] == 64
    specs = param_specs(cfg, params, MESH)
    assert tuple(specs["layers"]["attn"]["wq"])[2] == "model"
    assert all(ax is None for ax in tuple(specs["layers"]["attn"]["wk"]))


def test_head_padding_preserves_function():
    """Padded-head model == unpadded model exactly (dead slots masked)."""
    import dataclasses
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import reduced
    from repro.models.attention import q_head_layout
    from repro.models.transformer import init_model, loss_fn

    cfg0 = reduced(get_config("yi-34b"))
    group = cfg0.num_heads // cfg0.num_kv_heads
    cfg1 = dataclasses.replace(
        cfg0, padded_heads=cfg0.num_kv_heads * (group + 2))
    p0 = init_model(jax.random.PRNGKey(0), cfg0)
    p1 = init_model(jax.random.PRNGKey(0), cfg1)
    _, mask = q_head_layout(cfg1)
    idx = np.where(np.asarray(mask))[0]
    for name, ax in (("wq", 2), ("wo", 1)):
        a1 = np.array(p1["layers"]["attn"][name])
        sl = [slice(None)] * a1.ndim
        sl[ax] = idx
        a1[tuple(sl)] = np.array(p0["layers"]["attn"][name])
        p1["layers"]["attn"][name] = jnp.asarray(a1)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg0.vocab_size)
    l0 = loss_fn(p0, {"tokens": tok}, cfg0)
    l1 = loss_fn(p1, {"tokens": tok}, cfg1)
    np.testing.assert_allclose(np.asarray(l0), np.asarray(l1), rtol=1e-5)


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
@pytest.mark.parametrize("shape_name", list(INPUT_SHAPES))
def test_batch_and_cache_specs_fit(arch, shape_name):
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if not cfg.supports_shape(shape)[0]:
        pytest.skip("arch skips this shape")
    for mesh in (MESH, MESH_MP):
        bs = batch_specs(cfg, shape, mesh)
        from repro.models import input_specs as ispec
        abs_in = ispec(cfg, shape)
        for k, spec in bs.items():
            assert _fits(spec, abs_in[k].shape, mesh), (arch, shape_name, k)
        if shape.kind == "decode":
            cache = abstract_cache(cfg, shape)
            cs = cache_specs(cfg, shape, mesh, cache)

            def check(leaf, spec):
                assert _fits(spec, leaf.shape, mesh), (arch, shape_name,
                                                       leaf.shape, spec)

            jax.tree.map(check, cache, cs, is_leaf=lambda x: isinstance(x, P))


def test_small_mesh_end_to_end_lowering():
    """Real 2x2-device lowering+compile of a reduced arch (subprocess so the
    device-count flag can't leak into other tests)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config, reduced
from repro.models import abstract_params, input_specs, make_train_step, abstract_opt_state
from repro.optim.optimizers import make_optimizer
from repro import sharding as shd
from repro.configs.base import InputShape

from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(2, 2)
cfg = reduced(get_config("llama3.2-1b"))
shape = InputShape("small", 64, 4, "train")
params = abstract_params(cfg)
opt = make_optimizer("adamw", 1e-3)
opt_abs = abstract_opt_state(opt, params)
pspecs = shd.param_specs(cfg, params, mesh)
ospecs = shd.opt_state_specs(cfg, opt_abs, params, mesh)
bspecs = shd.batch_specs(cfg, shape, mesh)
sh = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                            is_leaf=lambda x: isinstance(x, P))
step = make_train_step(cfg, opt)
batch = input_specs(cfg, shape)
with jax.set_mesh(mesh):
    lowered = jax.jit(step, in_shardings=(sh(pspecs), sh(ospecs),
                                          NamedSharding(mesh, P()), sh(bspecs))
                      ).lower(params, opt_abs,
                              jax.ShapeDtypeStruct((), jnp.int32), batch)
    compiled = lowered.compile()
print("MEM", compiled.memory_analysis().temp_size_in_bytes)
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env={**__import__("os").environ,
                                        "PYTHONPATH": "src"},
                       cwd=__import__("os").path.dirname(
                           __import__("os").path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-3000:]
    assert "MEM" in r.stdout


# ---------------------------------------------------------------------------
# launch helpers: roofline peaks, compile cache
# ---------------------------------------------------------------------------


def test_chip_peaks_are_keyed_by_device_kind():
    from repro.launch.mesh import chip_peaks
    v5e = chip_peaks("TPU v5 lite")
    assert (v5e.flops_bf16, v5e.hbm_bandwidth) == (197e12, 819e9)
    with pytest.raises(KeyError, match="no peaks recorded"):
        chip_peaks("cpu")


@pytest.mark.parametrize("env_dir", [None, "named"])
def test_compile_cache_follows_env_else_checkout(env_dir, tmp_path,
                                                 monkeypatch):
    from repro.launch import compile_cache
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(compile_cache.DEFAULT_DIR)
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    try:
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          was[1])
    assert compile_cache.DEFAULT_DIR.name == ".jax_cache"
    assert (compile_cache.DEFAULT_DIR.parent / "chip_smoke.py").exists()
