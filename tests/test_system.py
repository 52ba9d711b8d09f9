"""End-to-end behaviour tests: training converges, checkpoint/restart is
bit-consistent with an uninterrupted run, serve loop generates."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.train import train


def test_training_loss_decreases(cpu_mesh, tmp_path):
    out = train("stablelm_1_6b", steps=30, seq_len=16, global_batch=2,
                smoke=True, mesh=cpu_mesh, log_every=100)
    first = np.mean(out["losses"][:5])
    last = np.mean(out["losses"][-5:])
    assert last < first - 0.1, f"no learning: {first} -> {last}"


def test_checkpoint_restart_matches_uninterrupted(cpu_mesh, tmp_path):
    """Train 20 steps straight vs 10 + crash + resume 10: same final loss."""
    d1 = str(tmp_path / "a")
    out_full = train("stablelm_1_6b", steps=20, seq_len=16, global_batch=2,
                     smoke=True, mesh=cpu_mesh, ckpt_dir=d1, ckpt_every=10,
                     log_every=100)

    d2 = str(tmp_path / "b")
    train("stablelm_1_6b", steps=20, seq_len=16, global_batch=2, smoke=True,
          mesh=cpu_mesh, ckpt_dir=d2, ckpt_every=10, log_every=100,
          stop_after=10)                       # simulated preemption
    out_resumed = train("stablelm_1_6b", steps=20, seq_len=16,
                        global_batch=2, smoke=True, mesh=cpu_mesh,
                        ckpt_dir=d2, ckpt_every=10, log_every=100)
    assert out_resumed["final_loss"] == pytest.approx(
        out_full["final_loss"], rel=1e-3)


def test_generation_loop(cpu_mesh, rules):
    """Prefill + N decode steps produce a coherent growing sequence."""
    from repro.configs import smoke_config
    from repro.launch.steps import (build_params, make_decode_step,
                                    make_prefill_step)
    from repro.models.transformer import pad_caches
    cfg = smoke_config("qwen3_8b")
    with cpu_mesh:
        params, _ = build_params(cfg, rules, abstract=False)
        toks = jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab, (1, 8)), jnp.int32)
        prefill = jax.jit(make_prefill_step(cfg, rules))
        decode = jax.jit(make_decode_step(cfg, rules))
        logits, caches = prefill(params, {"tokens": toks})
        # caches from prefill are prompt-sized; pad to the decode budget
        caches = pad_caches(caches, cfg, max_seq=16)
        cur = jnp.argmax(logits, axis=-1)[:, None]
        outs = []
        for i in range(4):
            nxt, logits_d, caches = decode(params, caches, cur,
                                           jnp.asarray(8 + i, jnp.int32))
            assert bool(jnp.all(jnp.isfinite(logits_d.astype(jnp.float32))))
            cur = nxt[:, None].astype(jnp.int32)
            outs.append(int(nxt[0]))
        assert all(0 <= t < cfg.padded_vocab for t in outs)


@pytest.mark.parametrize("env_dir", [None, "given"])
def test_compile_cache_directory(monkeypatch, tmp_path, env_dir):
    """The entry points' compile cache: ``$JAX_COMPILATION_CACHE_DIR`` when
    set, else the fixed ``.jax_cache/`` at the checkout root."""
    import os

    from repro.launch.compile_cache import enable_compile_cache
    root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(os.path.realpath(root), ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    was = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
