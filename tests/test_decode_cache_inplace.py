"""Decode carries the contiguous KV cache through the layer scan and writes
one token per row in place (``models/transformer.py``, DESIGN.md §19).

The oracle is the dense stack's decode as it was before: the stacked cache
passed through ``lax.scan`` as ``xs`` and every layer's written slab came
back as ``ys``. The carried formulation must give bit-equal logits and a
bit-equal cache after a prefill and several decode steps, on every path
that decodes a contiguous cache. A second test compiles the engine's
decode program and looks for whole-slab copies in it.
"""
import re
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import SHVSConfig, get_arch
from repro.models import dist
from repro.models import model as model_mod
from repro.models import transformer
from repro.models.attention import attention_block, cross_attention_block
from repro.models.layers import apply_mlp, layer_norm, rms_norm
from repro.models.model import Model
from repro.models.moe import apply_moe
from repro.models.transformer import (slice_stage_cache, slice_stage_params,
                                      stage_bounds)


def scan_xs_ys_decode(params, x, positions, cfg, cache, mode, window=None,
                      remat=False, enc_out=None, chunk_mask=None,
                      chunk_counts=None, final_norm=True):
    """``apply_dense_stack``'s decode on a contiguous cache as it was before
    the cache was carried: the cache goes through the scan as ``xs`` and
    each layer's whole written slab comes back as ``ys``."""
    assert mode == "decode" and "k" in cache
    if cfg.family == "audio":
        def norm(h, w):
            return layer_norm(h, w, jnp.zeros_like(w), cfg.rmsnorm_eps)
    else:
        def norm(h, w):
            return rms_norm(h, w, cfg.rmsnorm_eps)
    win = cfg.sliding_window if window is None else window
    kv_len = cache["len"] + 1
    lens0 = cache["len"]

    def write(c, t):
        slot = (lens0 % c.shape[1]).astype(jnp.int32)
        return jax.vmap(lambda cb, tb, s: jax.lax.dynamic_update_slice(
            cb, tb, (s, 0, 0)))(c, t, slot)

    def body(x, xs):
        lp = xs["layer"]
        h = norm(x, lp["ln1"])
        _, k, v = attention_block(lp["attn"], h, cfg, positions,
                                  mode="train", window=win)
        ck, cv = write(xs["ck"], k), write(xs["cv"], v)
        attn_out, _, _ = attention_block(
            lp["attn"], h, cfg, positions, cache_k=ck, cache_v=cv,
            kv_len=kv_len, mode="decode", window=win)
        x = x + attn_out
        if cfg.is_encdec:
            hc = norm(x, lp["ln_cross"])
            x = x + cross_attention_block(
                lp["cross"], hc, (xs["cross_k"], xs["cross_v"]), cfg)
        h2 = norm(x, lp["ln2"])
        if cfg.moe is not None:
            ff, _ = apply_moe(lp["moe"], h2, cfg, train=False)
        else:
            ff = apply_mlp(lp["mlp"], h2, cfg.act)
        x = x + ff
        x = dist.constrain(x, dist.batch_spec_entry(), None, None)
        return x, {"ck": ck, "cv": cv}

    xs = {"layer": {k: v for k, v in params.items() if k != "final_ln"},
          "ck": cache["k"], "cv": cache["v"]}
    if cfg.is_encdec:
        xs["cross_k"], xs["cross_v"] = cache["cross_k"], cache["cross_v"]
    x, ys = jax.lax.scan(body, x, xs)
    cache = dict(cache)
    cache["k"], cache["v"] = ys["ck"], ys["cv"]
    cache["len"] = cache["len"] + 1
    cache["pos"] = cache["pos"] + 1
    if final_norm:
        x = norm(x, params["final_ln"])
    return x, cache, jnp.zeros((), jnp.float32)


def _oracle(monkeypatch):
    """Route the model's dense decode through the scan-xs/ys oracle; every
    other mode keeps the stack under test (prefill is unchanged)."""
    real = transformer.apply_dense_stack

    def stack(params, x, positions, cfg, cache, mode, **kw):
        fn = scan_xs_ys_decode if mode == "decode" else real
        return fn(params, x, positions, cfg, cache, mode, **kw)

    monkeypatch.setattr(model_mod, "apply_dense_stack", stack)


B, SEQ, STEPS = 3, 32, 7
TRUE_LENS = np.array([6, 4, 5], np.int32)   # per-row slots differ


def _config(case):
    arch = {"llama": "smollm-360m", "qwen3": "qwen3-8b",
            "sliding": "starcoder2-7b", "moe": "granite-moe-1b-a400m",
            "whisper": "whisper-base", "stages": "qwen3-8b",
            "inactive": "smollm-360m"}[case]
    cfg = get_arch(arch).reduced()
    if case == "sliding":
        # an 8-slot ring: prefill fills up to 6 slots, 7 decode steps wrap
        cfg = replace(cfg, sliding_window=8)
    if case in ("llama", "inactive"):
        cfg = replace(cfg, num_kv_heads=2)
    return cfg


def _decode_run(case, slab_write, monkeypatch, oracle):
    """Prefill, then STEPS teacher-forced decode steps. Returns, per step,
    the logits and the cache leaves (every stage's, for ``stages``)."""
    with monkeypatch.context() as m:
        if oracle:
            _oracle(m)
        if slab_write:
            m.setattr(transformer, "_token_major", lambda cache_k: False)
        cfg = _config(case)
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(7)
        toks = rng.integers(1, cfg.vocab_size, (B, 6)).astype(np.int32)
        batch = {"tokens": jnp.asarray(toks)}
        if cfg.is_encdec:
            batch["frames"] = 0.1 * jax.random.normal(
                jax.random.PRNGKey(1),
                (B, cfg.encoder.num_frames, cfg.d_model))
        _, cache = jax.jit(lambda p, b, c: model.prefill(
            p, b, c, true_lens=jnp.asarray(TRUE_LENS)))(
                params, batch, model.init_cache(B, SEQ))
        actives = [np.ones(B, bool)] * STEPS
        if case == "inactive":
            actives = [np.array(a, bool) for a in
                       ([1, 1, 1], [1, 0, 1], [0, 1, 1], [1, 1, 0],
                        [0, 0, 1], [1, 1, 1], [1, 0, 0])]
        if case == "stages":
            bounds = stage_bounds(cfg.num_layers, 2)
            sps, caches = [], []
            for s, (lo, hi) in enumerate(bounds):
                sp = {"stack": slice_stage_params(params["stack"], lo, hi,
                                                  last=s == 1),
                      "emb": params["emb"]}
                sps.append(sp)
                caches.append(slice_stage_cache(cache, lo, hi))
            stage = [jax.jit(lambda p, x, c, s=s: model.decode_stage(
                p, x, c, first=s == 0, last=s == 1)) for s in range(2)]

            def step(tokens, active):
                x, caches[0] = stage[0](sps[0], tokens, caches[0])
                logits, caches[1] = stage[1](sps[1], x, caches[1])
                return logits, [c for c in caches]
        else:
            decode = jax.jit(lambda p, t, c, a: _decode_impl_like(
                model, p, t, c, a))

            def step(tokens, active):
                nonlocal cache
                logits, cache = decode(params, tokens, cache, active)
                return logits, [cache]

        out = []
        for t in range(STEPS):
            tokens = jnp.asarray(
                rng.integers(1, cfg.vocab_size, (B,)).astype(np.int32))
            logits, caches_now = step(tokens, jnp.asarray(actives[t]))
            out.append((np.asarray(logits),
                        [{k: np.asarray(v) for k, v in c.items()}
                         for c in caches_now]))
        return out


def _decode_impl_like(model, params, tokens, cache, active):
    """``Engine._decode_impl``'s forward half: inactive rows keep their
    cache write offset."""
    lens0 = cache["len"]
    logits, cache = model.decode_step(params, tokens, cache)
    cache = dict(cache)
    cache["len"] = jnp.where(active, lens0 + 1, lens0)
    return logits, cache


@pytest.mark.parametrize("case,slab_write", [
    ("llama", False), ("qwen3", False), ("sliding", False), ("moe", False),
    ("whisper", False), ("stages", False), ("inactive", False),
    ("llama", True), ("sliding", True), ("inactive", True),
], ids=["llama", "qwen3-qknorm", "sliding-ring-wraps", "moe", "whisper-cross",
        "two-stages", "inactive-rows", "llama-slab-write",
        "sliding-slab-write", "inactive-slab-write"])
def test_carried_decode_is_bit_identical_to_scan_xs_ys(case, slab_write,
                                                       monkeypatch):
    """Logits and every cache leaf (k, v, len, pos, cross_k/v) are equal bit
    for bit at each of 7 decode steps. ``slab_write`` forces the write that
    a device with the slot axis minor gets (the layer's slab written back
    whole) on the CPU, whose layouts always take the token scatter."""
    want = _decode_run(case, slab_write, monkeypatch, oracle=True)
    got = _decode_run(case, slab_write, monkeypatch, oracle=False)
    if case == "sliding":
        assert got[-1][1][0]["len"].min() > 8      # the ring wrapped
    for t, ((lw, cw), (lg, cg)) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(lg, lw, err_msg=f"logits, step {t}")
        for s, (a, b) in enumerate(zip(cw, cg)):
            assert a.keys() == b.keys()
            for k in a:
                np.testing.assert_array_equal(
                    b[k], a[k], err_msg=f"cache[{k!r}], stage {s}, step {t}")


# ---------------------------------------------------------------------------
# The compiled decode program holds no whole-slab copy
# ---------------------------------------------------------------------------

_INSTR = re.compile(r"^\s*(ROOT )?%(\S+) = (\([^=]*\)|\S+) ([\w-]+)\((.*)$")
_SHAPE = re.compile(r"\w+\[([\d,]*)\]")
_MOVES = {"copy", "dynamic-slice", "dynamic-update-slice"}


def _computations(hlo: str):
    """{name: [(is_root, name, type, opcode, rest)]} of an HLO module's
    text."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) ", line)
        if head and not line.startswith(" "):
            cur = comps.setdefault(head.group(1), [])
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            cur.append((bool(m.group(1)), m.group(2), m.group(3),
                        m.group(4), m.group(5)))
    return comps


def _root_opcodes(comp):
    """Opcodes a fused computation produces, seen through its root's
    bitcasts, reshapes and output tuple."""
    by_name = {i[1]: i for i in comp}
    todo = [i for i in comp if i[0]]
    ops = set()
    while todo:
        _, _, _, op, rest = todo.pop()
        if op in ("bitcast", "reshape", "tuple"):
            for arg in re.findall(r"%([\w.-]+)", rest.split("),")[0]):
                if arg in by_name:
                    todo.append(by_name[arg])
        else:
            ops.add(op)
    return ops


def slab_moves(hlo: str, slab, whole):
    """The instructions outside fused computations that copy, slice or
    update-slice a whole KV slab or the whole stacked cache: a ``copy``,
    ``dynamic-slice`` or ``dynamic-update-slice``, or a fusion that ends in
    one, whose result (or one of its outputs) has that shape. A slice fused
    into a fusion that ends elsewhere (the attention's dot) is not one, nor
    is a scatter (the in-place token write)."""
    comps = _computations(hlo)
    fused = set()
    for comp in comps.values():
        for *_, rest in comp:
            fused.update(re.findall(r"(?:calls|to_apply)=%([\w.-]+)", rest))
    shapes = {tuple(slab), tuple(whole)}
    found = []
    for cname, comp in comps.items():
        if cname in fused:
            continue
        for _, name, typ, op, rest in comp:
            ops = {op}
            if op == "fusion":
                callee = re.search(r"calls=%([\w.-]+)", rest).group(1)
                ops = _root_opcodes(comps[callee])
            if not ops & _MOVES:
                continue
            for dims in _SHAPE.findall(typ):
                d = tuple(int(n) for n in dims.split(",") if n)
                while d and d[0] == 1 and len(d) > len(slab):
                    d = d[1:]
                if d in shapes:
                    found.append(f"{name} ({'/'.join(sorted(ops))}) {typ}")
                    break
    return found


@pytest.mark.parametrize("formulation", ["carried", "scan-xs-ys"])
def test_decode_program_moves_no_whole_slab(formulation, monkeypatch):
    """Compile ``Engine._decode_jit`` with the cache donated, as on the
    chip, for a tiny f32 dense model on the CPU. The carried formulation
    compiles to no copy, dynamic-slice or dynamic-update-slice of a whole
    ``(B, Sc, nkv, hd)`` layer slab or of the whole stacked cache; the
    scan-xs/ys oracle compiles to several, which shows the check can see
    them. The CPU compiler is a proxy: its fusions and layouts are not the
    TPU's, and the chip's profiler trace decides (PERF.md)."""
    from repro.engine import engine as engine_mod
    from repro.engine.engine import Engine, EngineConfig
    monkeypatch.setattr(engine_mod, "_donates", lambda: True)
    if formulation == "scan-xs-ys":
        _oracle(monkeypatch)
    cfg = replace(get_arch("smollm-360m").reduced(), num_layers=3,
                  num_kv_heads=2)
    params = Model(cfg).init(jax.random.PRNGKey(0))
    Bq, S = 4, 64
    eng = Engine(cfg, params, EngineConfig(
        max_batch=Bq, max_seq_len=S, algorithm="shvs",
        shvs=SHVSConfig(hot_size=64), k_cap=64, prompt_bucket=8))
    try:
        hlo = eng._decode_jit.lower(
            eng.params, eng.cache, eng.pstate, eng.last_tokens,
            eng._sp.as_params(), eng._sp.bias_array(),
            jnp.zeros((Bq,), jnp.uint32), jnp.zeros((Bq,), jnp.int32),
            jnp.asarray(0, jnp.int32), jnp.ones((Bq,), bool),
        ).compile().as_text()
    finally:
        eng.close()
    slab = (Bq, S, cfg.num_kv_heads, cfg.resolved_head_dim)
    moves = slab_moves(hlo, slab, (cfg.num_layers,) + slab)
    if formulation == "carried":
        assert moves == []
    else:
        assert len(moves) >= 4, moves
