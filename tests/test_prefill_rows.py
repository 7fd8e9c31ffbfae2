"""Serving prefill over the rows it advances (``serve_step`` ``prefill_rows``
and ``ModelExecutor.prefill``).

The program built ``prefill_slots_per_step`` rows wide gives the advanced
slots what the full-batch program gives them, for attention (k/v/pos),
latent-attention (ckv/krope) and recurrent (h/state/conv) cache leaves,
on one device and on a ``(data=1, model=4)`` mesh of virtual CPU devices,
and leaves every other slot's cache rows as they were.  A slot decoding
near the end of its ring keeps the start of its context while another
slot takes a prefill chunk.
"""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _kv import token_major
from repro.configs import list_archs, smoke_config
from repro.core.slo import SLOPolicy
from repro.serving.engine import Engine, EngineConfig, ModelExecutor
from repro.serving.request import Request, RequestStatus
from repro.serving.serve_step import build_serve_fns

CFG = dataclasses.replace(smoke_config("qwen3-8b"), dtype="float32")
B, T, C = 8, 64, 16


def _arch(name):
    """The smoke config of ``name`` in f32.  An MoE config gets the
    capacity factor at which no expert can overflow (E / top_k): the
    GShard capacity of a group is set by its token count, so at the
    config's own factor a 2-row call and the padded 8-row call drop
    different tokens."""
    cfg = dataclasses.replace(smoke_config(name), dtype="float32")
    if cfg.moe is not None:
        m = cfg.moe
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            m, capacity_factor=m.num_experts / m.top_k))
    return cfg


def _copy(cache):
    return jax.tree.map(jnp.copy, cache)


def _host(cache):
    return jax.tree.map(np.asarray, cache)


def _rows(cache, slots):
    """Each leaf's rows ``slots`` along its batch dim (numpy)."""
    from repro.serving.serve_step import _batch_leaf
    return jax.tree_util.tree_map_with_path(
        lambda p, x: np.take(np.asarray(x), slots, axis=_batch_leaf(p, x)),
        cache)


def _filled(fns, params, rng, vocab):
    """A cache whose slots hold 0-48 tokens of random prompts."""
    cache = fns.init_cache()
    lengths = np.zeros(B, np.int32)
    for _ in range(3):
        n = rng.integers(0, C + 1, B).astype(np.int32)
        toks = rng.integers(1, vocab, (B, C)).astype(np.int32)
        _, _, cache = fns.prefill_chunk(params, cache, toks, lengths, n)
        lengths = lengths + n
    return cache, lengths


def _mesh():
    from repro.launch.mesh import make_mesh
    return make_mesh((1, 4), ("data", "model"), devices=jax.devices()[:4])


@pytest.mark.parametrize("arch,mesh", [
    ("qwen3-8b", None), ("qwen3-8b", "tp4"),
    ("deepseek-v2-lite-16b", None), ("mamba2-370m", None)])
@pytest.mark.parametrize("advanced", [[5], [2, 6]])
def test_rows_program_matches_full_batch(arch, mesh, advanced):
    cfg = _arch(arch)
    mesh = _mesh() if mesh else None
    fns = build_serve_fns(cfg, mesh, batch=B, max_len=T, prefill_chunk=C,
                          prefill_width=2)
    params = fns.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(len(advanced))
    cache, lengths = _filled(fns, params, rng, cfg.vocab_size)
    before = _host(cache)
    toks = rng.integers(1, cfg.vocab_size, (B, C)).astype(np.int32)
    valid_n = np.zeros(B, np.int32)
    valid_n[advanced] = [C, 7][:len(advanced)]

    nxt_f, last_f, full = fns.prefill_chunk(params, _copy(cache), toks,
                                            lengths, valid_n)
    pad = [s for s in range(B) if s not in advanced][:2 - len(advanced)]
    slots = np.asarray(advanced + pad, np.int32)
    n = np.where(np.arange(2) < len(advanced), valid_n[slots], 0)
    nxt_r, last_r, rows = fns.prefill_rows(
        params, _copy(cache), toks[slots], lengths[slots],
        n.astype(np.int32), slots)

    k = len(advanced)
    assert np.asarray(nxt_r)[:k].tolist() == np.asarray(nxt_f)[advanced] \
        .tolist()
    lf = np.asarray(last_f)[advanced]
    np.testing.assert_allclose(np.asarray(last_r)[:k], lf, rtol=0,
                               atol=1e-5 * np.abs(lf).max())
    for a, b in zip(jax.tree.leaves(_rows(rows, advanced)),
                    jax.tree.leaves(_rows(full, advanced))):
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=1e-5 * max(np.abs(b).max(), 1))
    others = [s for s in range(B) if s not in advanced]
    for a, b in zip(jax.tree.leaves(_rows(rows, others)),
                    jax.tree.leaves(_rows(before, others))):
        np.testing.assert_array_equal(a, b)


def _pos(cache):
    """The ``pos`` leaves of the cache, (layers, B, T) each."""
    out = []
    jax.tree_util.tree_map_with_path(
        lambda p, x: out.append(np.asarray(x))
        if str(p[-1].key) == "pos" else None, cache)
    return out


def _decode_near_ring_end(concurrent: bool):
    """Slot 0 holds 57 of 64 tokens and decodes; with ``concurrent``, slot
    1 first takes a 16-token prefill chunk.  Returns the cache after that
    chunk and slot 0's next decode logits."""
    ecfg = EngineConfig(max_slots=4, max_len=T, prefill_chunk=C,
                        prefill_slots_per_step=2, max_tenants=2)
    exe = ModelExecutor(CFG, ecfg, rng_seed=0)
    prompt = np.random.default_rng(3).integers(1, CFG.vocab_size, 57)
    lengths = np.zeros(4, np.int32)
    for start in range(0, 57, C):
        part = prompt[start:start + C]
        toks = np.zeros((4, C), np.int32)
        toks[0, :part.size] = part
        exe.prefill(toks, lengths, np.asarray([part.size, 0, 0, 0],
                                              np.int32))
        lengths[0] += part.size
    if concurrent:
        toks = np.zeros((4, C), np.int32)
        toks[1] = np.arange(1, C + 1)
        exe.prefill(toks, lengths, np.asarray([0, C, 0, 0], np.int32))
        lengths[1] = C
    pos = _pos(exe.cache)
    _, logits, _ = exe.fns.decode(
        exe.params, exe.cache, jnp.asarray([5, 0, 0, 0], jnp.int32),
        jnp.asarray(lengths), jnp.asarray([True, False, False, False]))
    return pos, np.asarray(logits[0])


def test_prefill_keeps_the_context_of_a_slot_near_its_ring_end():
    """57 + 16 > 64: a pad write of slot 0 during slot 1's chunk would
    wrap onto slot 0's first 9 entries."""
    pos, got = _decode_near_ring_end(concurrent=True)
    for p in pos:
        assert p[:, 0, :57].tolist() == [list(range(57))] * len(p)
    _, want = _decode_near_ring_end(concurrent=False)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 * np.abs(want).max())


def _executor(P, slots=B):
    ecfg = EngineConfig(max_slots=slots, max_len=T, prefill_chunk=C,
                        prefill_slots_per_step=P, max_tenants=4)
    return ModelExecutor(CFG, ecfg, rng_seed=0)


def test_more_rows_than_width_are_refused():
    """The engine never advances more slots than the program's width; a
    caller that asks for more is told so, and the cache stays as it was."""
    exe = _executor(2)
    before = _host(exe.cache)
    with pytest.raises(ValueError, match="3 slots to prefill"):
        exe.prefill(np.ones((B, C), np.int32), np.zeros(B, np.int32),
                    np.asarray([C, 0, 3, 0, 0, 9, 0, 0], np.int32))
    for a, b in zip(jax.tree.leaves(_host(exe.cache)),
                    jax.tree.leaves(before)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("P", [B, B + 3])
def test_width_of_every_slot_runs_the_rows_program(P):
    """At ``prefill_slots_per_step >= max_slots`` the program is B rows
    wide: the advanced slots get the full-batch program's tokens and
    rows, and the others keep theirs (no pad entries are written)."""
    exe = _executor(P)
    assert exe.width == B
    rng = np.random.default_rng(5)
    toks = rng.integers(1, CFG.vocab_size, (B, C)).astype(np.int32)
    lengths = np.full(B, T - 4, np.int32)
    valid_n = np.asarray([C, 0, 0, 7, 0, 0, 0, 2], np.int32)
    before = _copy(exe.cache)
    got = exe.prefill(toks, lengths, valid_n)
    want, _, full = exe.fns.prefill_chunk(exe.params, _copy(before), toks,
                                          lengths, valid_n)
    adv = np.flatnonzero(valid_n)
    assert got[adv].tolist() == np.asarray(want)[adv].tolist()
    for a, b in zip(jax.tree.leaves(_rows(exe.cache, adv)),
                    jax.tree.leaves(_rows(full, adv))):
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=1e-5 * max(np.abs(b).max(), 1))
    rest = np.flatnonzero(valid_n == 0)
    for a, b in zip(jax.tree.leaves(_rows(exe.cache, rest)),
                    jax.tree.leaves(_rows(before, rest))):
        np.testing.assert_array_equal(a, b)


def test_engine_refuses_an_executor_of_another_width():
    ecfg = EngineConfig(max_slots=4, max_len=T, prefill_chunk=C,
                        prefill_slots_per_step=3, max_tenants=2)
    with pytest.raises(ValueError, match="2 rows wide"):
        Engine(ecfg, executor=types.SimpleNamespace(width=2))
    Engine(ecfg, executor=types.SimpleNamespace(width=3))


class _FullBatch(ModelExecutor):
    """Every prefill call runs the whole ``(B, C)`` batch."""

    def prefill(self, tokens, lengths, valid_n):
        return self._call("test.prefill", "test.prefill.sync",
                          self.fns.prefill_chunk, tokens, lengths, valid_n)


def _serve(exe):
    ecfg = EngineConfig(max_slots=4, max_len=128, prefill_chunk=C,
                        prefill_slots_per_step=2, max_tenants=2)
    eng = Engine(ecfg, executor=exe)
    for t in (0, 1):
        eng.create_ectx(t, SLOPolicy(kv_quota_tokens=128 * 2))
    rng = np.random.default_rng(11)
    for i in range(8):
        eng.submit(Request(i % 2, rng.integers(1, CFG.vocab_size,
                                               5 + 7 * i).astype(np.int32),
                           max_new_tokens=6))
    eng.run_until_idle()
    assert all(r.status == RequestStatus.DONE for r in eng.done)
    return sorted((r.rid, tuple(r.generated)) for r in eng.done)


def test_engine_serves_the_tokens_of_the_full_batch_program():
    ecfg = EngineConfig(max_slots=4, max_len=128, prefill_chunk=C,
                        prefill_slots_per_step=2, max_tenants=2)
    assert _serve(ModelExecutor(CFG, ecfg, rng_seed=0)) == _serve(
        _FullBatch(CFG, ecfg, rng_seed=0))


# ---------------------------------------------------------------------------
# served tokens against the forward without a cache
# ---------------------------------------------------------------------------
DECODE_ARCHS = [a for a in list_archs()
                if not smoke_config(a).is_encoder_decoder]


def _serve_two_slots(fns, params, prompts, C, steps):
    """Prefill ``prompts`` (slot -> tokens) two rows at a time in C-token
    chunks, then decode ``steps`` tokens for every slot at once.  Returns
    the cache, each slot's lengths and each slot's (token, logits) per
    served position."""
    slots = sorted(prompts)
    lengths = np.zeros(B, np.int32)
    served = {s: [] for s in slots}
    cache = fns.init_cache()
    while any(lengths[s] < len(prompts[s]) for s in slots):
        toks = np.zeros((2, C), np.int32)
        n = np.zeros(2, np.int32)
        for r, s in enumerate(slots):
            part = prompts[s][lengths[s]:lengths[s] + C]
            toks[r, :part.size], n[r] = part, part.size
        nxt, last, cache = fns.prefill_rows(
            params, cache, toks, lengths[slots], n,
            np.asarray(slots, np.int32))
        for r, s in enumerate(slots):
            lengths[s] += n[r]
            if n[r] and lengths[s] == len(prompts[s]):
                served[s].append((int(nxt[r]), np.asarray(last[r])))
    active = np.zeros(B, bool)
    active[slots] = True
    for _ in range(steps):
        toks = np.zeros(B, np.int32)
        for s in slots:
            toks[s] = served[s][-1][0]
        nxt, logits, cache = fns.decode(params, cache, toks, lengths, active)
        for s in slots:
            served[s].append((int(nxt[s]), np.asarray(logits[s])))
        lengths = lengths + active
    return cache, lengths, served


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_served_tokens_match_the_forward_without_cache(arch):
    """``prefill_rows`` (two slots a call, one running out first) then six
    decode steps give each slot the tokens and logits of the plain
    forward over its whole sequence.  The smoke local-window layers keep
    32 entries: the 30-token prompt's last chunk [24, 36) holds 6 tokens
    and 6 pad entries that reach past the ring's end, and its last four
    decode steps wrap the ring.  Served again in 7-token chunks, every
    slot reads back the same cache, token-major, each position at its
    ring entry."""
    cfg = _arch(arch)
    steps = 6
    rng = np.random.default_rng(7)
    prompts = {1: rng.integers(1, cfg.vocab_size, 30).astype(np.int32),
               3: rng.integers(1, cfg.vocab_size, 20).astype(np.int32)}
    runs = []
    for C in (12, 7):
        fns = build_serve_fns(cfg, None, batch=B, max_len=T,
                              prefill_chunk=C, prefill_width=2)
        params = fns.init_params(jax.random.PRNGKey(0))
        runs.append(_serve_two_slots(fns, params, prompts, C, steps))
    (cache, lengths, served), (cache7, lengths7, served7) = runs

    for s, prompt in prompts.items():
        seq = np.concatenate([prompt, [t for t, _ in served[s][:-1]]])
        ref, _ = fns.model.forward(params, {"tokens": jnp.asarray(seq[None])})
        ref = np.asarray(ref[0, len(prompt) - 1:])
        for run in (served, served7):
            assert [t for t, _ in run[s]] == ref.argmax(-1).tolist()
            np.testing.assert_allclose(np.stack([lg for _, lg in run[s]]),
                                       ref, rtol=0,
                                       atol=1e-4 * np.abs(ref).max())
        assert lengths[s] == lengths7[s] == len(seq)

    rows = sorted(prompts)
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(
                _rows(token_major(cache), rows))[0],
            jax.tree.leaves(_rows(token_major(cache7), rows))):
        if str(path[-1].key) == "pos":                  # (..., 2, W)
            W = a.shape[-1]
            for i, s in enumerate(rows):
                want = [max((p for p in range(lengths[s]) if p % W == t),
                            default=-1) for t in range(W)]
                got = a[..., i, :].reshape(-1, W).tolist()
                assert got == [want] * len(got)
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, rtol=0,
                                       atol=1e-4 * max(np.abs(b).max(), 1))
