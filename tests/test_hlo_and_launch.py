"""HLO collective parser + mesh/step builders + cached-embedding LM."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_stats import collective_bytes, collective_stats

SAMPLE = """
HloModule jit_step
%add { ... }
  %p0 = f32[16,128]{1,0} parameter(0)
  %all-reduce.1 = f32[16,128]{1,0} all-reduce(%p0), replica_groups=[8,8]<=[64], to_apply=%add
  %ag = bf16[4,256]{1,0} all-gather(bf16[1,256]{1,0} %x), dimensions={0}
  %rs = f32[2,64]{1,0} reduce-scatter(%all-reduce.1), dimensions={0}
  %cp = u8[32]{0} collective-permute(%q), source_target_pairs={{0,1}}
  %a2a = (f32[8,8]{1,0}, f32[8,8]{1,0}) all-to-all(%m, %n), dimensions={0}
  %nothing = f32[2,2]{1,0} add(%p0, %p0)
"""


def test_parser_counts_and_bytes():
    st = collective_stats(SAMPLE)
    assert st["all-reduce"]["count"] == 1
    assert st["all-reduce"]["bytes_in"] == 16 * 128 * 4  # via symbol table
    assert st["all-gather"]["count"] == 1
    assert st["all-gather"]["bytes_in"] == 1 * 256 * 2  # inline operand shape
    assert st["all-gather"]["bytes_out"] == 4 * 256 * 2
    assert st["reduce-scatter"]["bytes_in"] == 16 * 128 * 4  # resolved by name
    assert st["reduce-scatter"]["bytes_out"] == 2 * 64 * 4
    assert st["collective-permute"]["count"] == 1
    assert st["all-to-all"]["count"] == 1
    assert st["total"]["count"] == 5
    assert collective_bytes(SAMPLE) == st["total"]["bytes_in"]


def test_parser_skips_done_ops():
    txt = """
  %s = (f32[4]{0}, f32[4]{0}) all-gather-start(f32[4]{0} %x), dimensions={0}
  %d = f32[4]{0} all-gather-done(%s)
"""
    st = collective_stats(txt)
    assert st["all-gather"]["count"] == 1  # -start counted, -done not


def test_make_production_mesh_shapes():
    # mesh construction itself needs >=512 devices; validate the spec only
    import inspect

    from repro.launch import mesh as M

    src = inspect.getsource(M.make_production_mesh)
    assert "(2, 16, 16)" in src and "(16, 16)" in src
    assert '"pod", "data", "model"' in src


def test_cached_embedding_lm_matches_full_embedding(mesh1):
    """ScratchPipe-cached input embedding == ordinary full-table SGD training
    (small LM, same seeds): the LM analogue of the paper's 'algorithm
    unchanged' claim."""
    import dataclasses

    from repro.configs import get_smoke_config
    from repro.core.cached_embedding import CachedEmbeddingLM
    from repro.core.host_table import HostEmbeddingTable
    from repro.core.pipeline import ScratchPipe
    from repro.data.lookahead import LookaheadStream
    from repro.models import api

    cfg = get_smoke_config("llama4-scout-17b-a16e")
    V, D = cfg.vocab_size, cfg.d_model
    steps, B, S = 10, 4, 16
    lr = 1e-2

    rng = np.random.default_rng(0)
    toks = rng.integers(0, V, size=(steps, B, S), dtype=np.int64)
    labels = np.roll(toks, -1, axis=2).astype(np.int32)

    # --- reference: full embedding trained on-device with plain SGD -------
    lm_ref = CachedEmbeddingLM(cfg, mesh1, jax.random.key(1), lr=lr, emb_lr=lr)
    host0 = HostEmbeddingTable(V, D, seed=0)
    full_embed = jax.device_put(host0.data)
    ref_losses = []
    with jax.set_mesh(mesh1):
        for i in range(steps):
            slots = jnp.asarray(toks[i])  # identity slot mapping
            full_embed, aux = lm_ref.train_fn(
                full_embed, slots, {"labels": jnp.asarray(labels[i])}
            )
            ref_losses.append(float(aux["loss"]))
    ref_params = lm_ref.params

    # --- ScratchPipe cached embedding --------------------------------------
    lm = CachedEmbeddingLM(cfg, mesh1, jax.random.key(1), lr=lr, emb_lr=lr)
    host = HostEmbeddingTable(V, D, seed=0)
    pipe = ScratchPipe(host, num_slots=192, train_fn=lm.train_fn)
    stream = LookaheadStream(
        iter(
            [
                (toks[i], {"labels": jnp.asarray(labels[i])})
                for i in range(steps)
            ]
        )
    )
    with jax.set_mesh(mesh1):
        stats = pipe.run(stream, lookahead_fn=stream.peek_ids)
    pipe.flush_to_host()

    losses = [float(s.aux["loss"]) for s in stats]
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-4)
    np.testing.assert_allclose(
        host.data, np.asarray(full_embed), atol=2e-5
    )
    for a, b in zip(jax.tree.leaves(lm.params), jax.tree.leaves(ref_params)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=2e-4
        )


@pytest.mark.parametrize("env_dir", [None, "/cache/from/env"])
def test_compile_cache_placement(monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins and is left to JAX; otherwise the cache
    sits at a fixed path inside the checkout (a moving path never hits)."""
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        got = compile_cache.setup_compile_cache()
        if env_dir is None:
            want = str(compile_cache.REPO_ROOT / ".jax_cache")
            assert got == want == jax.config.jax_compilation_cache_dir
            assert (compile_cache.REPO_ROOT / "pyproject.toml").exists()
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
