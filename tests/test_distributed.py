"""Distributed substrate: sharding rules, checkpoints (incl. ELASTIC
restore), quantized optimizer states, EF-int8 compression, overlapped
collectives, fault monitor, data pipeline determinism.

Multi-device cases run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main test process
must keep seeing 1 device — dry-run contract)."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get, reduced
from repro.launch.faults import FaultMonitor
from repro.data import SyntheticLM
from repro.models.model import init_params, param_shapes
from repro.optim.adamw import (AdamWConfig, adamw_update,
                               dequantize_blockwise, init_opt_state,
                               quantize_blockwise)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_subprocess(code: str, devices: int = 8) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ------------------------------------------------------------- sharding --
def test_param_specs_cover_all_archs():
    code = """
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.configs import ARCHS
    from repro.dist.sharding import param_specs
    from repro.models.model import param_shapes
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    for name, cfg in ARCHS.items():
        shapes = param_shapes(cfg)
        specs = param_specs(shapes, mesh, fsdp=True)
        import dataclasses
        cfg2 = dataclasses.replace(cfg, scan_layers=True)
        specs2 = param_specs(param_shapes(cfg2), mesh, fsdp=True)
        # all specs constructible and dims divide
        def check(sh, sp):
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            for dim, entry in enumerate(sp):
                if entry is None: continue
                axes = entry if isinstance(entry, tuple) else (entry,)
                prod = 1
                for a in axes: prod *= sizes[a]
                assert sh[dim] % prod == 0, (name, sh, sp)
        import numpy as np
        jax.tree.map(lambda s, p: check(s, p), shapes, specs,
                     is_leaf=lambda x: isinstance(x, tuple) and all(
                         isinstance(i, (int, np.integer)) for i in x))
    print("OK")
    """
    assert "OK" in run_subprocess(code)


def test_sharded_train_step_runs_on_8_devices():
    """Real (allocated) sharded train step on a 2x4 mesh: loss finite and
    matches the single-device value."""
    code = """
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get, reduced
    from repro.dist.sharding import batch_spec, param_specs, shard_params
    from repro.launch.mesh import make_local_mesh
    from repro.models.model import init_params, loss_fn
    from repro.optim.adamw import AdamWConfig, init_opt_state
    from repro.train.loop import TrainConfig, make_train_step
    from jax.sharding import NamedSharding

    cfg = reduced(get("gemma2-2b"))
    cfg = dataclasses.replace(cfg, dp_axes=("data",), tp_axis="model",
                              scan_layers=True)
    mesh = make_local_mesh(model_parallel=4)      # (2, 4), Auto axes
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = dict(tokens=jax.random.randint(jax.random.PRNGKey(1), (4, 32),
                                           0, cfg.vocab))
    ref_loss = float(loss_fn(params, batch, cfg))

    with mesh:
        sp = shard_params(params, mesh, fsdp=True)
        sb = jax.device_put(batch["tokens"],
                            NamedSharding(mesh, batch_spec(mesh)))
        opt_cfg = AdamWConfig()
        opt = init_opt_state(sp, opt_cfg)
        step = jax.jit(make_train_step(cfg, opt_cfg, TrainConfig()))
        p2, o2, metrics = step(sp, opt, dict(tokens=sb))
        loss = float(metrics["loss"])
    assert abs(loss - ref_loss) / abs(ref_loss) < 1e-3, (loss, ref_loss)
    print("OK", loss)
    """
    assert "OK" in run_subprocess(code)


# ----------------------------------------------------------- checkpoints --
def test_checkpoint_roundtrip(tmp_path):
    from repro.ckpt import latest_step, restore_checkpoint, save_checkpoint
    cfg = reduced(get("h2o-danube-1.8b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = init_opt_state(params, AdamWConfig())
    tree = dict(p=params, o=opt)
    save_checkpoint(str(tmp_path), 42, tree)
    assert latest_step(str(tmp_path)) == 42
    restored = restore_checkpoint(str(tmp_path), 42, tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_elastic_restore_across_mesh_shapes(tmp_path):
    """Save on an 8-device (2,4) mesh, restore onto (4,2) AND (1,8):
    elastic re-sharding via global arrays."""
    code = f"""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get, reduced
    from repro.dist.sharding import param_specs, shard_params
    from repro.models.model import init_params
    from repro.ckpt import restore_checkpoint, save_checkpoint

    cfg = reduced(get("h2o-danube-1.8b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh1 = jax.make_mesh((2, 4), ("data", "model"))
    sp = shard_params(params, mesh1, fsdp=True)
    save_checkpoint({str(tmp_path)!r}, 1, sp)

    for shape in [(4, 2), (1, 8)]:
        mesh2 = jax.make_mesh(shape, ("data", "model"))
        specs2 = param_specs(params, mesh2, fsdp=True)
        restored = restore_checkpoint({str(tmp_path)!r}, 1, params,
                                      mesh=mesh2, specs=specs2)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("OK")
    """
    assert "OK" in run_subprocess(code)


def test_train_resume_reproduces(tmp_path):
    """checkpoint/restart: 4 steps straight == 2 steps + resume + 2."""
    from repro.train import TrainConfig, train
    cfg = reduced(get("h2o-danube-1.8b"), n_layers=2)
    opt_cfg = AdamWConfig(lr_peak=1e-3, warmup_steps=2, total_steps=10)
    data = SyntheticLM(cfg.vocab, 16, 4, seed=3)
    params = init_params(jax.random.PRNGKey(0), cfg)

    pA, _, _ = train(cfg, opt_cfg, TrainConfig(), data, params, 4)

    d1 = str(tmp_path / "resume")
    tc = TrainConfig(ckpt_dir=d1, ckpt_every=2)
    pB, _, _ = train(cfg, opt_cfg, tc, data, params, 2)
    pB2, _, _ = train(cfg, opt_cfg, tc, data, params, 4)  # resumes at 2
    for a, b in zip(jax.tree.leaves(pA), jax.tree.leaves(pB2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


# ------------------------------------------------------ quantized states --
def test_blockwise_quant_roundtrip():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(1000) * 0.01, jnp.float32)
    q, s, shp = quantize_blockwise(x)
    y = dequantize_blockwise(q, s, shp)
    assert q.dtype == jnp.int8
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=2e-4)


def test_quantized_adamw_tracks_fp32():
    cfg = reduced(get("h2o-danube-1.8b"), n_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    grads = jax.tree.map(lambda p: jnp.ones_like(p) * 1e-3, params)
    for quant in [False, True]:
        ocfg = AdamWConfig(quantized_state=quant, lr_peak=1e-3,
                           warmup_steps=1)
        st = init_opt_state(params, ocfg)
        p1, st, _ = adamw_update(params, grads, st, ocfg)
        if quant:
            p_q = p1
        else:
            p_f = p1
    err = max(float(jnp.abs(a - b).max())
              for a, b in zip(jax.tree.leaves(p_q), jax.tree.leaves(p_f)))
    assert err < 1e-4


# ----------------------------------------------------------- compression --
def test_compressed_psum_approximates_mean():
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.optim.compression import compressed_psum

    mesh = jax.make_mesh((8,), ("data",))
    rng = np.random.default_rng(0)
    g = rng.standard_normal((8, 1024)).astype(np.float32)

    def body(x):
        out, err = compressed_psum(x[0], "data")
        return out, err[None]
    f = jax.shard_map(body, mesh=mesh, in_specs=P("data", None),
                      out_specs=(P(), P("data", None)), check_vma=False)
    out, err = f(g)
    expect = g.mean(axis=0)
    rel = np.abs(np.asarray(out) - expect).max() / np.abs(expect).max()
    assert rel < 0.05, rel
    # error feedback: residual + transmitted == original contribution
    print("OK", rel)
    """
    assert "OK" in run_subprocess(code)


def test_collective_matmul_overlap_hlo():
    """The ring collective-matmul lowers to while{dot, collective-permute}
    (overlap), not {all-gather, dot}."""
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.dist.collectives import collective_matmul_ag

    mesh = jax.make_mesh((8,), ("model",))
    x = jnp.ones((64, 32), jnp.float32)
    w = jnp.ones((32, 16), jnp.float32)
    f = jax.shard_map(lambda xs, ws: collective_matmul_ag(xs, ws, "model"),
                      mesh=mesh, in_specs=(P("model", None), P(None, None)),
                      out_specs=P(None, None), check_vma=False)
    with mesh:
        lowered = jax.jit(f).lower(x, w)
        compiled = lowered.compile()
    text = compiled.as_text()
    assert "collective-permute" in text
    out = jax.jit(f)(x, w)
    np.testing.assert_allclose(np.asarray(out)[:8],
                               np.asarray(x @ w)[:8], rtol=1e-6)
    # result must equal all_gather(x) @ w = x @ w here (x replicated rows)
    print("OK")
    """
    assert "OK" in run_subprocess(code)


def test_ring_all_reduce_correct():
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.dist.collectives import ring_all_reduce

    mesh = jax.make_mesh((8,), ("d",))
    rng = np.random.default_rng(1)
    g = rng.standard_normal((8, 37)).astype(np.float32)
    f = jax.shard_map(lambda x: ring_all_reduce(x[0], "d"),
                      mesh=mesh, in_specs=P("d", None), out_specs=P(),
                      check_vma=False)
    out = f(g)
    np.testing.assert_allclose(np.asarray(out), g.sum(0), rtol=1e-5)
    print("OK")
    """
    assert "OK" in run_subprocess(code)


def test_ring_reduce_scatter_and_all_gather_index_aligned():
    """Device d's reduce-scatter output is chunk d, and ring_all_gather
    places shard d at index d — composing them reassembles the plain
    all-reduce with no block permutation."""
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.dist.collectives import ring_all_gather, ring_reduce_scatter

    mesh = jax.make_mesh((4,), ("d",))
    rng = np.random.default_rng(2)
    g = rng.standard_normal((4, 8, 3)).astype(np.float32)

    f = jax.shard_map(lambda x: ring_reduce_scatter(x[0], "d"),
                      mesh=mesh, in_specs=P("d", None, None),
                      out_specs=P("d", None), check_vma=False)
    out = np.asarray(f(g))                       # [8, 3] re-concatenated
    np.testing.assert_allclose(out, g.sum(0), rtol=1e-5)

    f2 = jax.shard_map(
        lambda x: ring_all_gather(ring_reduce_scatter(x[0], "d"), "d")
                  .reshape(8, 3),
        mesh=mesh, in_specs=P("d", None, None), out_specs=P(),
        check_vma=False)
    np.testing.assert_allclose(np.asarray(f2(g)), g.sum(0), rtol=1e-5)
    print("OK")
    """
    assert "OK" in run_subprocess(code, devices=4)


# -------------------------------------------------------- fault tolerance --
def test_fault_monitor_straggler_detection():
    m = FaultMonitor(straggler_factor=3.0)
    t = 0.0
    for step in range(10):
        m.heartbeat(step, now=t)
        t += 1.0
    assert not m.is_straggling
    m.heartbeat(10, now=t + 10.0)     # 10x the EMA step time
    assert m.is_straggling
    assert m.straggler_events[0]["step"] == 10


def test_preemption_checkpoints_and_exits(tmp_path):
    from repro.ckpt import latest_step
    from repro.train import TrainConfig, train
    cfg = reduced(get("h2o-danube-1.8b"), n_layers=2)
    data = SyntheticLM(cfg.vocab, 16, 4, seed=3)
    params = init_params(jax.random.PRNGKey(0), cfg)
    monitor = FaultMonitor()
    monitor.inject_preemption()
    tc = TrainConfig(ckpt_dir=str(tmp_path))
    train(cfg, AdamWConfig(), tc, data, params, 50, monitor=monitor)
    # exited after the first step with a checkpoint on disk
    assert latest_step(str(tmp_path)) == 1


# ---------------------------------------------------------- data pipeline --
def test_data_pipeline_deterministic_and_sharded():
    a = SyntheticLM(1000, 32, 8, seed=5).batch_at(17)
    b = SyntheticLM(1000, 32, 8, seed=5).batch_at(17)
    np.testing.assert_array_equal(np.asarray(a["tokens"]),
                                  np.asarray(b["tokens"]))
    # shards partition the stream deterministically and differ
    s0 = SyntheticLM(1000, 32, 8, seed=5, n_shards=2, shard=0).batch_at(17)
    s1 = SyntheticLM(1000, 32, 8, seed=5, n_shards=2, shard=1).batch_at(17)
    assert s0["tokens"].shape == (4, 32)
    assert not np.array_equal(np.asarray(s0["tokens"]),
                              np.asarray(s1["tokens"]))


def test_prefetcher_overlaps():
    from repro.data import Prefetcher
    src = SyntheticLM(100, 8, 2, seed=1)
    pf = Prefetcher(src, start_step=3)
    step, batch = pf.next()
    assert step == 3
    np.testing.assert_array_equal(np.asarray(batch["tokens"]),
                                  np.asarray(src.batch_at(3)["tokens"]))
    pf.close()
