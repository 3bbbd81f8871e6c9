"""Property-based tests (hypothesis) on the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.config import SamplingConfig
from repro.core import penalties as pen
from repro.engine.pipeline import MicrobatchPlanner
from repro.engine.request import Request
from repro.core.sampling import (SamplingParams, filter_mask_reference,
                                 masked_probs_reference,
                                 truncation_first_sample)
from repro.core.shvs import make_hot_set, shvs_masses, shvs_sample
from repro.core.sizing import SizingModel, fit_affine_cost
from repro.engine.paged_cache import BlockAllocator, PagedCacheConfig

SETTINGS = dict(max_examples=25, deadline=None)


def _z(data, B, V, scale=3.0):
    seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(0, scale, (B, V)).astype(np.float32)), rng


@given(st.data())
@settings(**SETTINGS)
def test_histogram_update_commutes(data):
    """Order of incremental updates never matters (Eq. 5 is a sum)."""
    V = data.draw(st.integers(4, 64))
    B = data.draw(st.integers(1, 4))
    T = data.draw(st.integers(1, 8))
    seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, V, (T, B))
    s1 = pen.init_state(B, V)
    for t in range(T):
        s1 = pen.update_histograms(s1, jnp.asarray(toks[t]))
    s2 = pen.init_state(B, V)
    for t in rng.permutation(T):
        s2 = pen.update_histograms(s2, jnp.asarray(toks[t]))
    np.testing.assert_array_equal(np.asarray(s1.output_counts),
                                  np.asarray(s2.output_counts))


@given(st.data())
@settings(**SETTINGS)
def test_penalties_never_raise_seen_positive_logits(data):
    """Penalties only make seen tokens less likely (for λ_rep ≥ 1, λ ≥ 0)."""
    V, B = 32, 3
    z, rng = _z(data, B, V)
    prompts = jnp.asarray(rng.integers(0, V, (B, 5)))
    state = pen.init_state(B, V, prompt_tokens=prompts)
    lam = data.draw(st.floats(1.0, 3.0))
    pres = data.draw(st.floats(0.0, 2.0))
    freq = data.draw(st.floats(0.0, 2.0))
    out = pen.apply_penalties(z, state, SamplingConfig(
        repetition_penalty=lam, presence_penalty=pres, frequency_penalty=freq))
    seen = np.asarray(state.prompt_mask | state.output_mask)
    z_np, out_np = np.asarray(z), np.asarray(out)
    assert (out_np[seen] <= z_np[seen] + 1e-5).all()
    unseen_same = np.isclose(out_np[~seen], z_np[~seen], atol=1e-5)
    assert unseen_same.all()


@given(st.data())
@settings(**SETTINGS)
def test_truncation_support_equals_reference_support(data):
    """Whenever the truncation declares itself exact, its kept-set size must
    equal the reference filter support exactly."""
    B = data.draw(st.integers(1, 6))
    V = data.draw(st.sampled_from([32, 64, 128]))
    z, rng = _z(data, B, V)
    top_k = data.draw(st.sampled_from([0, 3, 8, 16]))
    top_p = data.draw(st.sampled_from([1.0, 0.85, 0.95]))
    min_p = data.draw(st.sampled_from([0.0, 0.05]))
    temp = data.draw(st.floats(0.3, 1.5))
    params = SamplingParams.broadcast(B, SamplingConfig(
        temperature=temp, top_k=top_k, top_p=top_p, min_p=min_p))
    res = truncation_first_sample(z, params, jnp.full((B,), 0.37), k_cap=V)
    mask = filter_mask_reference(z / max(temp, 1e-6), params)
    exact = np.asarray(res.exact)
    kept, ref = np.asarray(res.kept), np.asarray(mask.sum(-1))
    assert (kept[exact] == ref[exact]).all()


@given(st.data())
@settings(**SETTINGS)
def test_trunc_token_in_reference_support(data):
    B, V = 4, 64
    z, rng = _z(data, B, V)
    top_k = data.draw(st.sampled_from([2, 5, 10]))
    u = jnp.asarray(rng.random(B).astype(np.float32))
    params = SamplingParams.broadcast(B, SamplingConfig(temperature=0.8,
                                                        top_k=top_k))
    toks = np.asarray(truncation_first_sample(z, params, u, k_cap=32).tokens)
    mask = np.asarray(filter_mask_reference(z / 0.8, params))
    assert mask[np.arange(B), toks].all()


@given(st.data())
@settings(**SETTINGS)
def test_shvs_masses_partition_total(data):
    """S_hot + S_tail == full softmax normalizer, for any hot set."""
    B = data.draw(st.integers(1, 4))
    V = data.draw(st.sampled_from([32, 96, 256]))
    H = data.draw(st.integers(1, V - 1))
    z, rng = _z(data, B, V)
    hot = make_hot_set(jnp.asarray(np.sort(rng.choice(V, H, replace=False)),
                                   jnp.int32), V)
    m, s_hot, s_tail, tail_max = shvs_masses(z, hot)
    total = np.exp(np.asarray(z) - np.asarray(m)[:, None]).sum(-1)
    np.testing.assert_allclose(np.asarray(s_hot + s_tail), total, rtol=1e-4)
    alpha = np.asarray(s_hot / (s_hot + s_tail))
    assert ((alpha >= 0) & (alpha <= 1)).all()


@given(st.data())
@settings(max_examples=10, deadline=None)
def test_shvs_tokens_in_support(data):
    """SHVS never emits a token outside the reference filter support when
    every row is exact (guard passed or fallback exact)."""
    B, V, H = 3, 96, 24
    z, rng = _z(data, B, V)
    hot_idx = jnp.asarray(np.sort(rng.choice(V, H, replace=False)), jnp.int32)
    hot = make_hot_set(hot_idx, V)
    top_k = data.draw(st.sampled_from([4, 10]))
    params = SamplingParams.broadcast(B, SamplingConfig(temperature=0.9,
                                                        top_k=top_k))
    u = jnp.asarray(rng.random((B, 3)).astype(np.float32))
    r = shvs_sample(z, params, hot, u[:, 0], u[:, 1], u[:, 2], k_cap=48)
    mask = np.asarray(filter_mask_reference(z / 0.9, params))
    ok = ~np.asarray(r.needs_reference)
    toks = np.asarray(r.tokens)
    assert mask[np.arange(B), toks][ok].all()


@pytest.mark.paged
@given(st.data())
@settings(max_examples=40, deadline=None)
def test_block_allocator_invariants(data):
    """Arbitrary allocate/free interleavings (DESIGN.md §9): a block is
    never double-allocated, free + live always partitions the pool, and
    exhaustion is reported deterministically and atomically (a failing
    ensure mutates nothing)."""
    num_blocks = data.draw(st.integers(1, 24))
    block_size = data.draw(st.sampled_from([1, 2, 4, 16]))
    max_per_seq = data.draw(st.integers(1, 12))
    batch = data.draw(st.integers(1, 5))
    pcfg = PagedCacheConfig(block_size=block_size, num_blocks=num_blocks,
                            max_blocks_per_seq=max_per_seq)
    alloc = BlockAllocator(pcfg, batch)
    lengths = [0] * batch

    def check_invariants():
        live = [b for owned in alloc.owned for b in owned]
        assert len(live) == len(set(live)), "double-allocated block"
        assert not set(live) & set(alloc.free), "block both live and free"
        assert len(live) + len(alloc.free) == num_blocks, \
            "pool leaked or grew"
        for slot in range(batch):
            assert len(alloc.owned[slot]) == alloc.blocks_needed(
                lengths[slot]) or lengths[slot] == 0

    for _ in range(data.draw(st.integers(1, 40))):
        slot = data.draw(st.integers(0, batch - 1))
        if data.draw(st.booleans()):
            target = lengths[slot] + data.draw(st.integers(0, 3 * block_size))
            need = alloc.blocks_needed(target)
            grow = need - len(alloc.owned[slot])
            must_fail = need > max_per_seq or grow > len(alloc.free)
            free_before = list(alloc.free)
            owned_before = [list(b) for b in alloc.owned]
            try:
                alloc.ensure(slot, target)
                assert not must_fail, "ensure succeeded past exhaustion"
                lengths[slot] = max(lengths[slot], target)
            except RuntimeError:
                assert must_fail, "spurious exhaustion report"
                assert alloc.free == free_before, "failed ensure mutated free"
                assert alloc.owned == owned_before, \
                    "failed ensure leaked a partial allocation"
        else:
            alloc.release(slot)
            lengths[slot] = 0
        check_invariants()


@pytest.mark.disagg
@given(st.data())
@settings(max_examples=40, deadline=None)
def test_block_allocator_migrate_roundtrip_conserves_pools(data):
    """Export/import round-trips (DESIGN.md §18): ``export_slot`` hands
    back every owned block exactly once and returns them all to the
    source free list (no leaks, no double-frees); the importer consumes
    exactly ``blocks_needed(T)`` fresh blocks on an independent pool, and
    releasing the landed slot restores that pool too — an arbitrary
    interleaving of migrations conserves both allocators."""
    num_blocks = data.draw(st.integers(2, 24))
    block_size = data.draw(st.sampled_from([1, 2, 4, 16]))
    batch = data.draw(st.integers(1, 4))
    pcfg = PagedCacheConfig(block_size=block_size, num_blocks=num_blocks,
                            max_blocks_per_seq=num_blocks)
    src, dst = BlockAllocator(pcfg, batch), BlockAllocator(pcfg, batch)

    def check(alloc):
        live = [b for owned in alloc.owned for b in owned]
        assert len(live) == len(set(live)), "double-allocated block"
        assert not set(live) & set(alloc.free), "block both live and free"
        assert len(live) + len(alloc.free) == num_blocks, "pool leaked"

    lengths = {}
    for slot in range(batch):
        target = data.draw(st.integers(0, 3 * block_size))
        if target == 0:
            continue
        try:
            src.ensure(slot, target)
            lengths[slot] = target
        except RuntimeError:
            pass
        check(src)

    for slot in data.draw(st.permutations(sorted(lengths))):
        T = lengths[slot]
        owned_before = list(src.owned[slot])
        src_free_before = len(src.free)
        blocks = src.export_slot(slot)
        # every owned block handed over exactly once, then freed on the
        # source: the exporter's pool is whole again for this slot
        assert blocks == owned_before
        assert len(blocks) == len(set(blocks))
        assert len(blocks) == src.blocks_needed(T)
        assert not src.owned[slot]
        assert len(src.free) == src_free_before + len(blocks)
        check(src)
        # the importer allocates FRESH ids on its own pool — block ids
        # never travel with the payload
        dst_free_before = len(dst.free)
        try:
            dst.ensure(slot, T)
        except RuntimeError:
            check(dst)
            continue
        assert len(dst.owned[slot]) == dst.blocks_needed(T)
        assert len(dst.free) == dst_free_before - dst.blocks_needed(T)
        check(dst)
        if data.draw(st.booleans()):        # decode finishes → release
            dst.release(slot)
            assert len(dst.free) == dst_free_before
            check(dst)
    # after every migration the source pool is fully free again
    assert sorted(src.free) == list(range(num_blocks))


@pytest.mark.pipeline
@given(st.data())
@settings(max_examples=40, deadline=None)
def test_microbatch_planner_invariants(data):
    """Arbitrary dispatch/idle schedules through the pipeline's cycle
    clock (DESIGN.md §12): no slot is ever covered by two in-flight
    microbatches, no token commits before its microbatch's re-entry
    cycle, and per-slot commit order matches the single-stage engine's
    (tokens land in exactly the order they were dispatched). The planner
    enforces the first two with internal assertions — this test drives it
    through random schedules (partial activity, idle microbatches, p=1
    degenerate pipelines) so a ledger bug trips them."""
    p = data.draw(st.integers(1, 4))
    M = p * data.draw(st.integers(1, 3))
    R = data.draw(st.integers(1, 3))
    planner = MicrobatchPlanner(p, M, R)
    requests = {}
    for slot in range(M * R):
        r = Request(request_id=slot, prompt=[1], max_new_tokens=1 << 30)
        r.slot = slot
        requests[slot] = r
    fed = [0] * (M * R)          # next per-slot sequence number to dispatch
    committed = [[] for _ in range(M * R)]
    stage_pos = {}               # mb -> stage holding its activation
    sampled = {}                 # mb -> {slot: seq} awaiting re-entry commit

    def mark_exit(i, active_slots):
        planner.mark_exit(i)
        sampled[i] = {}
        for slot in active_slots:
            sampled[i][slot] = fed[slot]
            fed[slot] += 1

    n_cycles = data.draw(st.integers(1, 50))
    for cycle in range(n_cycles + 2 * (M + p)):
        draining = cycle >= n_cycles
        c = planner.cycle
        for s in range(p - 1, -1, -1):
            i = planner.stage_for(c, s)
            if s > 0:
                if stage_pos.get(i) == s:
                    if s == p - 1:
                        rec = planner.inflight[i]
                        mark_exit(i, [r.slot for a, r in
                                      zip(rec.active, rec.slot_request)
                                      if a])
                        del stage_pos[i]
                    else:
                        stage_pos[i] = s + 1
                continue
            # s == 0: re-entry — commit, then maybe dispatch
            if i in sampled:
                rec = planner.commit(i)
                assert planner.cycle >= rec.exit_cycle + 1
                for slot, seq in sampled.pop(i).items():
                    committed[slot].append(seq)
            if draining or i in stage_pos:
                continue
            group = list(planner.group_slots(i))
            active = np.array([data.draw(st.booleans()) for _ in group])
            if not active.any():
                continue
            planner.dispatch(i, active, [requests[g] for g in group],
                             np.zeros(len(group), np.uint32),
                             np.zeros(len(group), np.int32))
            if p == 1:
                mark_exit(i, [g for g, a in zip(group, active) if a])
            else:
                stage_pos[i] = 1
        planner.tick()
    assert not planner.inflight and not sampled and not stage_pos, \
        "drain left tokens in flight"
    for slot in range(M * R):
        # single-stage order: position k commits before position k+1,
        # nothing skipped, nothing duplicated
        assert committed[slot] == list(range(fed[slot]))


@given(st.data())
@settings(**SETTINGS)
def test_affine_fit_recovers_parameters(data):
    c0 = data.draw(st.floats(1e-7, 1e-3))
    c = data.draw(st.floats(1e-10, 1e-6))
    hs = np.asarray([128, 512, 2048, 8192, 16384], np.float64)
    times = c0 + c * hs
    c0_fit, c_fit = fit_affine_cost(hs, times)
    assert abs(c0_fit - c0) < 1e-6 + 0.01 * c0
    assert abs(c_fit - c) < 1e-12 + 0.01 * c


@pytest.mark.kernels
@given(st.data())
@settings(max_examples=20, deadline=None)
def test_fused_single_pass_equals_unfused_composition(data):
    """The fused Pallas kernel ≡ the unfused ``kernels/ref.py`` composition
    BITWISE on all four outputs (tokens, exact, alpha, kept) across shapes,
    dtypes, block sizes, hot-set densities, and adversarial logits (±inf
    injections, fully-masked rows, τ=0 greedy rows, top_k=1 forced rows).
    The oracle walks the same vocab tiles with the same helpers, so any
    drift — a missed re-basis, a stale operand, a reordered accumulation —
    breaks exact equality."""
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    B = data.draw(st.integers(1, 5))
    V = data.draw(st.sampled_from([128, 192, 384, 512, 1024]))
    block_v = data.draw(st.sampled_from([128, 256, 512]))
    k_cap = data.draw(st.sampled_from([8, 16, 64, 200]))
    dtype = data.draw(st.sampled_from([jnp.float32, jnp.bfloat16]))
    hot_frac = data.draw(st.sampled_from([0.0, 0.25, 1.0]))
    seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)

    z = rng.normal(0, 4, (B, V)).astype(np.float32)
    if data.draw(st.booleans()):          # adversarial injections
        z.flat[rng.integers(0, z.size, 3)] = np.inf
        z.flat[rng.integers(0, z.size, 3)] = -np.inf
        z[rng.integers(0, B)] = -1e30     # an all-masked row
    z = jnp.asarray(z).astype(dtype)
    cp = jnp.asarray(rng.integers(0, 3, (B, V)), jnp.int32)
    co = jnp.asarray(rng.integers(0, 3, (B, V)), jnp.int32)
    temp = rng.uniform(0.3, 1.5, B).astype(np.float32)
    top_k = rng.integers(0, 32, B).astype(np.int32)
    if data.draw(st.booleans()):
        temp[rng.integers(0, B)] = 0.0    # a greedy row
        top_k[rng.integers(0, B)] = 1     # a forced row
    params = SamplingParams(
        temperature=jnp.asarray(temp),
        top_k=jnp.asarray(top_k),
        top_p=jnp.asarray(rng.uniform(0.7, 1.0, B), jnp.float32),
        min_p=jnp.asarray(rng.uniform(0.0, 0.1, B), jnp.float32),
        repetition_penalty=jnp.asarray(rng.uniform(1.0, 2.0, B),
                                       jnp.float32),
        presence_penalty=jnp.asarray(rng.uniform(0, 1, B), jnp.float32),
        frequency_penalty=jnp.asarray(rng.uniform(0, 0.5, B), jnp.float32))
    u = jnp.asarray(rng.random(B), jnp.float32)
    hot = jnp.asarray(rng.random(V) < hot_frac)

    got = ops.fused_sample(z, cp, co, params, u, hot, k_cap=k_cap,
                           block_v=block_v)
    want = ref.fused_sample_ref(
        z, cp, co, params.repetition_penalty, params.presence_penalty,
        params.frequency_penalty, params.temperature, params.top_k,
        params.top_p, params.min_p, u, hot, k_cap=k_cap, block_v=block_v)
    for g, w, name in zip(got, want, ("tokens", "exact", "alpha", "kept")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)
    toks = np.asarray(got[0])
    assert ((toks >= 0) & (toks < V)).all()


@given(st.data())
@settings(max_examples=15, deadline=None)
def test_sizing_model_hstar_is_argmin(data):
    """H* from the first-order condition must (approximately) minimize F."""
    s = data.draw(st.floats(1.02, 1.5))
    V = 16384
    ranks = np.arange(1, V + 1, dtype=np.float64)
    p = ranks ** (-s)
    p /= p.sum()
    cum = np.cumsum(p)
    hs = np.unique(np.geomspace(8, V, 64).astype(np.int64))
    model = SizingModel(c0=1e-6, c=1e-9, vocab_size=V,
                        alpha_hs=hs.astype(np.float64), alpha_vals=cum[hs - 1])
    h_star = model.optimal_h()
    grid = np.arange(8, V, 64)
    f_min = model.expected_cost(grid).min()
    assert model.expected_cost(h_star) <= f_min * 1.02


@pytest.mark.kernels
@given(st.data())
@settings(max_examples=40, deadline=None)
def test_topk_merge_equals_stable_sort_merge(data):
    """The max-extraction merge ≡ concatenating buffer and tile and taking
    a stable descending sort's first K — including exact ties (values
    drawn from a small set) and the fresh buffer's ``-inf`` padding."""
    import jax.numpy as jnp
    from repro.kernels import ref

    bb = data.draw(st.integers(1, 4))
    K = data.draw(st.sampled_from([1, 3, 8, 16]))
    bv = data.draw(st.sampled_from([4, 8, 32]))
    earlier = data.draw(st.integers(0, 2 * K))
    seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    pool = np.float32([-np.inf, -1.0, 0.0, 0.5, 2.0])
    sentinel = earlier + bv               # the kernel's padded-vocab id

    def stable_top(vals, ids):
        order = np.argsort(-vals, axis=-1, kind="stable")[:, :K]
        return (np.take_along_axis(vals, order, -1),
                np.take_along_axis(ids, order, -1))

    # the running buffer: a previous merge of ``earlier`` lower ids into
    # the -inf / sentinel initial buffer
    init_v = np.full((bb, K), -np.inf, np.float32)
    init_i = np.full((bb, K), sentinel, np.int32)
    prev_v = rng.choice(pool, (bb, earlier)).astype(np.float32)
    prev_i = np.broadcast_to(np.arange(earlier, dtype=np.int32),
                             (bb, earlier))
    vals, idx = stable_top(np.concatenate([init_v, prev_v], -1),
                           np.concatenate([init_i, prev_i], -1))
    tile_v = rng.choice(pool, (bb, bv)).astype(np.float32)
    tile_i = np.broadcast_to(earlier + np.arange(bv, dtype=np.int32),
                             (bb, bv))
    want_v, want_i = stable_top(np.concatenate([vals, tile_v], -1),
                                np.concatenate([idx, tile_i], -1))
    got_v, got_i = ref.topk_merge(jnp.asarray(vals), jnp.asarray(idx),
                                  jnp.asarray(tile_v), jnp.asarray(tile_i))
    np.testing.assert_array_equal(np.asarray(got_v), want_v)
    np.testing.assert_array_equal(np.asarray(got_i), want_i)
