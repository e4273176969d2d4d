"""Serving throughput tier (round 18): chunked prefill into the paged
KV layout, speculative multi-token decode with verify/rollback, and
paged-KV quantization at rest.

Four layers:

* **append layer** — the `kv_cache_append` page-boundary regression
  (lengths pinned at page-size multiples: the token that exactly fills
  a slot's last page ADVANCES through the block table and lands; only
  the one past capacity is masked, in-function), plus the multi-token
  append's per-token page walk across boundaries;
* **kernel layer** — `flash_decode_multi` bit-identical to k sequential
  single-token launches (the all-accept contract), rollback restoring
  page bytes exactly, `flash_prefill`'s pools bit-identical to a
  `kv_cache_append` token loop at `kv_cache_dtype="off"` with fp64
  oracle parity for the chunk attention, counted unpaged fallbacks;
* **model layer** — the tp-sharded speculative/prefill steps: k=1
  byte-identical to the round-13 decode step, all-accept k>1 matching k
  sequential steps bitwise, rejection restoring `DecodeState` exactly,
  admission-through-prefill traces;
* **quantization layer** — DecodeState admission/retirement/growth
  churn against int8/bf16 page pools (fp64 oracle parity within codec
  tolerance; bit-exact at "off"), in-kernel dequant vs the gathered
  reference, the `page % 32` int8 geometry rule, register wiring.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accl_tpu.models import decode as dm
from accl_tpu.obs import metrics
from accl_tpu.ops import flash

WORLD = 8


def _counter(key: str) -> float:
    return metrics.snapshot()["counters"].get(key, 0.0)


def _mk(rng, *shape, scale=0.1):
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32)
                       * np.float32(scale))


def _mk_paged(rng, hkv, B, pages_max, page, d, shuffle=True, dtype=None):
    n_pages = B * pages_max
    kp = _mk(rng, hkv, n_pages, page, d)
    vp = _mk(rng, hkv, n_pages, page, d)
    if dtype is not None:
        kp, vp = kp.astype(dtype), vp.astype(dtype)
    perm = (rng.permutation(n_pages) if shuffle
            else np.arange(n_pages)).astype(np.int32)
    bt = jnp.asarray(perm.reshape(B, pages_max))
    return kp, vp, bt


def _multi_ref(q, kp, vp, bt, lens, span):
    """fp64 host oracle for the span kernel: row j of slot b attends
    positions 0 .. lens[b]-span+j inclusive."""
    q = np.asarray(q, np.float64)
    kpn = np.asarray(flash.dequantize_kv(kp), np.float64)
    vpn = np.asarray(flash.dequantize_kv(vp), np.float64)
    bt, lens = np.asarray(bt), np.asarray(lens)
    B, span_, H, d = q.shape
    hkv = kpn.shape[0]
    g = H // hkv
    out = np.zeros((B, span_, H, d))
    for b in range(B):
        chain_k = kpn[:, bt[b]].reshape(hkv, -1, d)
        chain_v = vpn[:, bt[b]].reshape(hkv, -1, d)
        for j in range(span_):
            ln = lens[b] - span_ + 1 + j
            if ln <= 0:
                continue
            for h in range(H):
                s = chain_k[h // g, :ln] @ q[b, j, h] / np.sqrt(d)
                s -= s.max()
                w = np.exp(s)
                w /= w.sum()
                out[b, j, h] = w @ chain_v[h // g, :ln]
    return out


# ---------------------------------------------------------------------------
# append layer: the page-boundary regression (satellite) + multi append
# ---------------------------------------------------------------------------

def test_kv_cache_append_exact_page_fill_advances(rng):
    """Lengths pinned at page-size multiples: the token that exactly
    fills a page — including the slot's LAST page — must be WRITTEN
    (advancing through the block table), never masked; the first token
    of the next page advances to the next table entry; only the token
    past capacity is masked, and that guard is in-function now."""
    B, hkv, d, page, pmax = 4, 2, 128, 8, 2
    cap = pmax * page
    kp, vp, bt = _mk_paged(rng, hkv, B, pmax, page, d)
    before = np.asarray(kp).copy()
    # slot 0: page-1 -> fills first page; slot 1: page -> first row of
    # page 2; slot 2: cap-1 -> fills the LAST page; slot 3: cap -> the
    # only masked case
    lens = jnp.asarray([page - 1, page, cap - 1, cap], jnp.int32)
    k_new = _mk(rng, B, hkv, d, scale=1.0)
    v_new = _mk(rng, B, hkv, d, scale=1.0)
    kp2, vp2, lens2 = flash.kv_cache_append(kp, vp, bt, lens, k_new,
                                            v_new)
    assert list(np.asarray(lens2)) == [page, page + 1, cap, cap]
    kp2_np, bt_np = np.asarray(kp2), np.asarray(bt)
    # slot 0: last row of its FIRST page (exact fill — written)
    np.testing.assert_array_equal(kp2_np[:, bt_np[0, 0], page - 1],
                                  np.asarray(k_new)[0])
    # slot 1: first row of its SECOND page (advanced through the table)
    np.testing.assert_array_equal(kp2_np[:, bt_np[1, 1], 0],
                                  np.asarray(k_new)[1])
    # slot 2: last row of its LAST page (exact fill of the last page)
    np.testing.assert_array_equal(kp2_np[:, bt_np[2, 1], page - 1],
                                  np.asarray(k_new)[2])
    # slot 3 (at capacity): NOTHING moved anywhere in its pages, length
    # pinned — the in-function guard, no caller mask needed
    for j in range(pmax):
        np.testing.assert_array_equal(kp2_np[:, bt_np[3, j]],
                                      before[:, bt_np[3, j]])


def test_kv_cache_append_multi_page_walk(rng):
    """The multi-token append walks the block table PER TOKEN: a span
    crossing a page boundary (and one exactly filling the last page)
    lands each token at bt[b, (len+j)//page] row (len+j)%page — bit-
    identical to sequential single appends; per-slot ``count`` and the
    capacity guard mask per token."""
    B, hkv, d, page, pmax, T = 3, 2, 128, 8, 2, 5
    cap = pmax * page
    kp, vp, bt = _mk_paged(rng, hkv, B, pmax, page, d)
    # slot 0 crosses page 0 -> 1 mid-span; slot 1 exactly fills the
    # last page at span end (cap-T .. cap-1); slot 2 overflows: only
    # cap - (cap-3) = 3 of 5 tokens land
    lens = jnp.asarray([page - 2, cap - T, cap - 3], jnp.int32)
    kn = _mk(rng, B, T, hkv, d)
    vn = _mk(rng, B, T, hkv, d)
    kp_m, vp_m, lens_m = flash.kv_cache_append_multi(kp, vp, bt, lens,
                                                     kn, vn)
    kp_s, vp_s, lens_s = kp, vp, lens
    for j in range(T):
        kp_s, vp_s, lens_s = flash.kv_cache_append(kp_s, vp_s, bt,
                                                   lens_s, kn[:, j],
                                                   vn[:, j])
    assert list(np.asarray(lens_m)) == list(np.asarray(lens_s)) \
        == [page - 2 + T, cap, cap]
    np.testing.assert_array_equal(np.asarray(kp_m), np.asarray(kp_s))
    np.testing.assert_array_equal(np.asarray(vp_m), np.asarray(vp_s))
    # count: only the first count[b] tokens land
    kp_c, _, lens_c = flash.kv_cache_append_multi(
        kp, vp, bt, lens, kn, vn, count=jnp.asarray([2, 0, 1]))
    assert list(np.asarray(lens_c)) == [page, cap - T, cap - 2]
    kp_c2, _, lens_c2 = flash.kv_cache_append_multi(
        kp, vp, bt, lens, kn[:, :2], vn[:, :2],
        active=jnp.asarray([True, False, True]))
    assert list(np.asarray(lens_c2)) == [page, cap - T, cap - 1]


# ---------------------------------------------------------------------------
# kernel layer: the span kernel + rollback + prefill
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("H,hkv,k", [(4, 4, 2), (8, 2, 3)])
def test_flash_decode_multi_bit_identical_to_sequential(rng, H, hkv, k):
    """The all-accept contract: one span-k launch == k sequential
    single-token append+decode launches, BIT-identical — dense and GQA,
    per-slot lengths crossing page boundaries."""
    B, d, page, pmax = 3, 128, 8, 4
    kp, vp, bt = _mk_paged(rng, hkv, B, pmax, page, d)
    lens0 = jnp.asarray([0, 7, 13], jnp.int32)
    qs = _mk(rng, B, k, H, d)
    kn = _mk(rng, B, k, hkv, d)
    vn = _mk(rng, B, k, hkv, d)
    kp_s, vp_s, lens_s = kp, vp, lens0
    outs = []
    for j in range(k):
        kp_s, vp_s, lens_s = flash.kv_cache_append(kp_s, vp_s, bt,
                                                   lens_s, kn[:, j],
                                                   vn[:, j])
        outs.append(flash.flash_decode(qs[:, j], kp_s, vp_s, bt, lens_s))
    kp_m, vp_m, lens_m = flash.kv_cache_append_multi(kp, vp, bt, lens0,
                                                     kn, vn)
    multi = flash.flash_decode_multi(qs, kp_m, vp_m, bt, lens_m)
    np.testing.assert_array_equal(np.asarray(multi),
                                  np.asarray(jnp.stack(outs, axis=1)))
    # and the fp64 oracle agrees
    np.testing.assert_allclose(np.asarray(multi),
                               _multi_ref(qs, kp_m, vp_m, bt, lens_m, k),
                               rtol=2e-5, atol=2e-5)
    # span=1 delegates to the single-query kernel byte-identically
    one = flash.flash_decode_multi(qs[:, :1], kp, vp, bt,
                                   jnp.maximum(lens0, 1))
    ref = flash.flash_decode(qs[:, 0], kp, vp, bt, jnp.maximum(lens0, 1))
    np.testing.assert_array_equal(np.asarray(one[:, 0]), np.asarray(ref))


def test_flash_decode_multi_fallback_counted(rng):
    """Span geometry the plan refuses (page % 8 != 0) falls back to the
    reference, counted under the decode fallback counter; unpaged mode
    counts reason=mode. Values still match the fp64 oracle."""
    B, H, hkv, k, d, page, pmax = 2, 4, 2, 2, 128, 12, 2
    kp, vp, bt = _mk_paged(rng, hkv, B, pmax, page, d)
    lens = jnp.asarray([5, 9], jnp.int32)
    q = _mk(rng, B, k, H, d)
    geo = 'accl_flash_decode_fallback_total{reason="geometry"}'
    mode = 'accl_flash_decode_fallback_total{reason="mode"}'
    g0, m0 = _counter(geo), _counter(mode)
    out = flash.flash_decode_multi(q, kp, vp, bt, lens)
    assert _counter(geo) == g0 + 1
    np.testing.assert_allclose(np.asarray(out),
                               _multi_ref(q, kp, vp, bt, lens, k),
                               rtol=2e-5, atol=2e-5)
    flash.flash_decode_multi(q, kp, vp, bt, lens, decode_mode="unpaged")
    assert _counter(mode) == m0 + 1


def test_kv_cache_rollback_restores_exactly(rng):
    """Rollback after a span append restores lengths AND page bytes to
    exactly the accepted-prefix state — bit-equal to having appended
    only the accepted tokens; accept == span is the identity."""
    B, hkv, d, page, pmax, k = 3, 2, 128, 8, 3, 3
    kp, vp, bt = _mk_paged(rng, hkv, B, pmax, page, d)
    lens0 = jnp.asarray([6, 0, 15], jnp.int32)   # crosses boundaries
    kn = _mk(rng, B, k, hkv, d)
    vn = _mk(rng, B, k, hkv, d)
    saved_k, saved_v = flash.kv_cache_read_rows(kp, vp, bt, lens0, k)
    kp_m, vp_m, lens_m = flash.kv_cache_append_multi(kp, vp, bt, lens0,
                                                     kn, vn)
    for accept in ([0, 1, 2], [3, 3, 3], [2, 0, 3]):
        acc = jnp.asarray(accept, jnp.int32)
        kp_r, vp_r, lens_r = flash.kv_cache_rollback(
            kp_m, vp_m, bt, lens_m, saved_k, saved_v, acc, k)
        # expected: only accept[b] tokens ever appended
        kp_e, vp_e, lens_e = flash.kv_cache_append_multi(
            kp, vp, bt, lens0, kn, vn, count=acc)
        assert list(np.asarray(lens_r)) == list(np.asarray(lens_e))
        np.testing.assert_array_equal(np.asarray(kp_r), np.asarray(kp_e))
        np.testing.assert_array_equal(np.asarray(vp_r), np.asarray(vp_e))


def test_flash_prefill_pools_bit_exact_and_oracle(rng):
    """The acceptance pin: chunked prefill's page pools match a
    kv_cache_append token loop BIT-exactly at kv_cache_dtype="off", and
    the chunk attention matches the fp64 causal oracle — across TWO
    chunks (the positional online-softmax carry: chunk 1's rows attend
    chunk 0's pages)."""
    H, hkv, d, page, pmax = 4, 2, 128, 8, 4
    B, C = 2, 2 * page
    kp, vp, bt = _mk_paged(rng, hkv, B, pmax, page, d)
    kp, vp = jnp.zeros_like(kp), jnp.zeros_like(vp)
    lens = jnp.zeros((B,), jnp.int32)
    slot = 1
    chunks = [(_mk(rng, C, H, d), _mk(rng, C, hkv, d), _mk(rng, C, hkv, d))
              for _ in range(2)]
    # paged prefill, two chunks
    kp_p, vp_p, lens_p, outs = kp, vp, lens, []
    for q, kc, vc in chunks:
        o, kp_p, vp_p, lens_p = flash.flash_prefill(
            q, kc, vc, kp_p, vp_p, bt, lens_p, slot)
        outs.append(o)
    assert list(np.asarray(lens_p)) == [0, 2 * C]
    # the token loop over the same stream
    kp_l, vp_l, lens_l = kp, vp, lens
    act = jnp.asarray([False, True])
    for _, kc, vc in chunks:
        for t in range(C):
            kn = jnp.zeros((B, hkv, d), jnp.float32).at[slot].set(kc[t])
            vn = jnp.zeros((B, hkv, d), jnp.float32).at[slot].set(vc[t])
            kp_l, vp_l, lens_l = flash.kv_cache_append(
                kp_l, vp_l, bt, lens_l, kn, vn, active=act)
    np.testing.assert_array_equal(np.asarray(kp_p), np.asarray(kp_l))
    np.testing.assert_array_equal(np.asarray(vp_p), np.asarray(vp_l))
    # fp64 oracle over the whole 2C-token prompt
    k_all = np.concatenate([np.asarray(c[1], np.float64)
                            for c in chunks])
    v_all = np.concatenate([np.asarray(c[2], np.float64)
                            for c in chunks])
    g = H // hkv
    for n, (q, _, _) in enumerate(chunks):
        qn = np.asarray(q, np.float64)
        for t in range(C):
            pos = n * C + t
            for h in range(H):
                s = k_all[:pos + 1, h // g] @ qn[t, h] / np.sqrt(d)
                s -= s.max()
                w = np.exp(s)
                w /= w.sum()
                ref = w @ v_all[:pos + 1, h // g]
                np.testing.assert_allclose(
                    np.asarray(outs[n])[t, h], ref, rtol=2e-5, atol=2e-5)


def test_flash_prefill_partial_chunk_and_fallback(rng):
    """A final partial chunk (live < C) writes/advances only the live
    rows; the unpaged mode and a plan-refused geometry fall back
    counted, with identical pool updates either way."""
    H, hkv, d, page, pmax = 4, 2, 128, 8, 2
    B, C = 2, page
    kp, vp, bt = _mk_paged(rng, hkv, B, pmax, page, d)
    kp, vp = jnp.zeros_like(kp), jnp.zeros_like(vp)
    lens = jnp.zeros((B,), jnp.int32)
    q, kc, vc = _mk(rng, C, H, d), _mk(rng, C, hkv, d), _mk(rng, C, hkv, d)
    out_f, kp_f, vp_f, lens_f = flash.flash_prefill(
        q, kc, vc, kp, vp, bt, lens, 0)
    out_p, kp_pp, vp_pp, lens_pp = flash.flash_prefill(
        q, kc, vc, kp, vp, bt, lens, 0, live=C - 3)
    assert list(np.asarray(lens_pp)) == [C - 3, 0]
    # live rows' outputs match the full-chunk run (their horizons never
    # reach the unwritten tail)
    np.testing.assert_array_equal(np.asarray(out_p)[:C - 3],
                                  np.asarray(out_f)[:C - 3])
    mode_k = 'accl_flash_prefill_fallback_total{reason="mode"}'
    m0 = _counter(mode_k)
    out_u, kp_u, vp_u, lens_u = flash.flash_prefill(
        q, kc, vc, kp, vp, bt, lens, 0, prefill_mode="unpaged")
    assert _counter(mode_k) == m0 + 1
    np.testing.assert_array_equal(np.asarray(kp_u), np.asarray(kp_f))
    assert list(np.asarray(lens_u)) == list(np.asarray(lens_f))
    np.testing.assert_allclose(np.asarray(out_u), np.asarray(out_f),
                               rtol=2e-5, atol=2e-5)
    # a chunk that is not page-granular declines with reason=geometry
    geo_k = 'accl_flash_prefill_fallback_total{reason="geometry"}'
    g0 = _counter(geo_k)
    flash.flash_prefill(q[:5], kc[:5], vc[:5], kp, vp, bt, lens, 0)
    assert _counter(geo_k) == g0 + 1


def test_prefill_plan_policy():
    """Plan pins: page-granular chunks only; the auto pick is the
    largest fitting page multiple <= 512; int8 pools tighten the page
    rule; VMEM miss declines."""
    plan, r = flash.prefill_plan(8, 2, 128, 8, 4, chunk=16)
    assert r == "ok" and plan["chunk"] == 16 and plan["gp"] == 64
    assert flash.prefill_plan(8, 2, 128, 8, 4, chunk=12) \
        == (None, "geometry")
    plan, r = flash.prefill_plan(8, 2, 128, 64, 4)
    assert r == "ok" and plan["chunk"] % 64 == 0 and plan["chunk"] <= 512
    # int8 pools: page % 32 rule (8 fails, 32 passes)
    assert flash.prefill_plan(8, 2, 128, 8, 4, chunk=8,
                              kv_itemsize=1) == (None, "geometry")
    plan, r = flash.prefill_plan(8, 2, 128, 32, 4, chunk=32,
                                 kv_itemsize=1)
    assert r == "ok"
    # a giant span busts the VMEM budget
    assert flash.prefill_plan(8, 1, 512, 512, 64, itemsize=4,
                              chunk=512 * 16)[0] is None


# ---------------------------------------------------------------------------
# model layer: spec + prefill steps on the tp mesh
# ---------------------------------------------------------------------------

def _setup(rng, slots=4, d_model=64, H=8, Hkv=4, hd=128, page=8,
           pmax=2, tp=2, kv_dtype=None):
    params = dm.init_decode_params(jax.random.PRNGKey(0), d_model, H,
                                   Hkv, hd)
    state = dm.init_decode_state(slots, pmax, page, Hkv, hd,
                                 kv_dtype=kv_dtype)
    mesh = dm.make_decode_mesh(jax.devices()[:tp], tp)
    return params, state, mesh


def test_spec_step_k1_byte_identical_to_decode_step(rng):
    """The k=1 pin: the speculative step at span 1 with an all-true
    draft mask IS the round-13 decode step — output and every state
    leaf byte-identical."""
    params, state, mesh = _setup(rng)
    state = dm.admit(dm.admit(state, 0), 2)
    p_sh, s_sh = dm.shard_decode(params, state, mesh)
    step = dm.build_decode_step(mesh)
    spec = dm.build_spec_decode_step(mesh, 1)
    x = _mk(rng, 4, 64)
    y, s1 = step(p_sh, s_sh, x)
    y1, sp1 = spec(p_sh, s_sh, x[:, None, :], np.ones((4, 1), bool))
    np.testing.assert_array_equal(np.asarray(y1[:, 0]), np.asarray(y))
    for a, b in zip(sp1, s1):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_spec_step_all_accept_matches_sequential(rng):
    """All-accept at k=3 == three sequential decode steps, bit-
    identical in outputs and state (the acceptance criterion)."""
    k = 3
    params, state, mesh = _setup(rng)
    state = dm.admit(dm.admit(state, 0), 3)
    p_sh, s_sh = dm.shard_decode(params, state, mesh)
    step = dm.build_decode_step(mesh)
    spec = dm.build_spec_decode_step(mesh, k)
    xs = _mk(rng, 4, k, 64)
    ys, sps = spec(p_sh, s_sh, xs, np.ones((4, k), bool))
    ss, youts = s_sh, []
    for j in range(k):
        yj, ss = step(p_sh, ss, xs[:, j])
        youts.append(yj)
    np.testing.assert_array_equal(np.asarray(ys),
                                  np.asarray(jnp.stack(youts, axis=1)))
    for a, b in zip(sps, ss):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_spec_step_rollback_restores_state(rng):
    """A rejection mid-span: lengths advance by the accepted prefix
    only and the rejected tokens' page rows are restored EXACTLY — the
    post-step state is bit-equal to a run that only ever appended the
    accepted tokens; parity with the unsharded oracle throughout."""
    k = 3
    params, state, mesh = _setup(rng)
    state = dm.admit(dm.admit(state, 0), 2)
    p_sh, s_sh = dm.shard_decode(params, state, mesh)
    spec = dm.build_spec_decode_step(mesh, k)
    xs = _mk(rng, 4, k, 64)
    ok = np.ones((4, k), bool)
    ok[0, 1] = False          # slot 0 accepts 1 of 3
    ok[2, 0] = False          # slot 2 accepts 0 of 3
    ys, sps = spec(p_sh, s_sh, xs, ok)
    assert list(np.asarray(sps.seq_lens)) == [1, 0, 0, 0]
    y_ref, sp_ref = dm.spec_step_reference(params, state, xs,
                                           jnp.asarray(ok))
    np.testing.assert_allclose(np.asarray(ys), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(sps.seq_lens),
                                  np.asarray(sp_ref.seq_lens))
    np.testing.assert_allclose(np.asarray(sps.k_pages),
                               np.asarray(sp_ref.k_pages),
                               rtol=2e-5, atol=2e-5)
    # bit-exact check at the flash level: rerun with accept-count
    # appends only (the sharded step's own pools)
    ss2 = s_sh
    ys2, sps2 = spec(p_sh, ss2, xs, np.ones((4, k), bool))
    # rejected rows differ from the all-accept run only where rolled
    # back; accepted prefix pages match bit-exactly
    kp_a, kp_r = np.asarray(sps2.k_pages), np.asarray(sps.k_pages)
    bt0 = np.asarray(state.block_tables)[0]
    page = state.k_pages.shape[2]
    # slot 0 accepted token 0: its row (pos 0 -> page bt0[0] row 0)
    np.testing.assert_array_equal(kp_r[:, bt0[0], 0], kp_a[:, bt0[0], 0])
    # pos 1 and 2 rolled back to the INITIAL zeros
    np.testing.assert_array_equal(kp_r[:, bt0[0], 1:3], 0.0)


def test_spec_step_declines_full_slots(rng):
    """A slot that cannot fit the whole span declines: no write, no
    advance, zeroed output — the full_slots eviction signal."""
    k = 3
    params, state, mesh = _setup(rng, page=8, pmax=1)   # cap = 8
    state = dm.admit(dm.admit(state, 0), 1)
    state = state._replace(
        seq_lens=state.seq_lens.at[0].set(7))   # 7 + 3 > 8: declines
    p_sh, s_sh = dm.shard_decode(params, state, mesh)
    spec = dm.build_spec_decode_step(mesh, k)
    xs = _mk(rng, 4, k, 64)
    before = np.asarray(s_sh.k_pages).copy()
    ys, sps = spec(p_sh, s_sh, xs, np.ones((4, k), bool))
    assert list(np.asarray(sps.seq_lens)) == [7, k, 0, 0]
    np.testing.assert_array_equal(np.asarray(ys[0]), 0.0)
    bt0 = np.asarray(state.block_tables)[0]
    np.testing.assert_array_equal(np.asarray(sps.k_pages)[:, bt0],
                                  before[:, bt0])


def test_prefill_step_then_decode_trace(rng):
    """Admission through chunked prefill: admit -> two prefill chunks
    -> decode steps continue the sequence; the paged state matches an
    unsharded oracle built by the reference step over the same stream,
    and the per-phase dispatch histograms tick."""
    params, state, mesh = _setup(rng, page=8, pmax=4)
    state = dm.admit(state, 1)
    p_sh, s_sh = dm.shard_decode(params, state, mesh)
    pre = dm.build_prefill_step(mesh)
    step = dm.build_decode_step(mesh)
    C = 8

    def hist(path):
        h = metrics.snapshot()["histograms"].get(
            f'accl_latency_dispatch_seconds{{path="{path}"}}')
        return h["count"] if h else 0

    pc0, dc0 = hist("prefill"), hist("decode")
    t0 = _counter('accl_serving_tokens_total{phase="prefill",'
                  'accepted="true"}')
    ss = s_sh
    for _ in range(2):
        xp = _mk(rng, C, 64)
        yp, ss = pre(p_sh, ss, xp, 1)
    assert hist("prefill") == pc0 + 2
    assert _counter('accl_serving_tokens_total{phase="prefill",'
                    'accepted="true"}') == t0 + 2 * C
    assert list(np.asarray(ss.seq_lens)) == [0, 2 * C, 0, 0]
    # decode continues from the prefilled cache
    x = _mk(rng, 4, 64)
    y, ss2 = step(p_sh, ss, x)
    assert hist("decode") == dc0 + 1
    assert list(np.asarray(ss2.seq_lens)) == [0, 2 * C + 1, 0, 0]
    # oracle: the reference decode step FROM the prefilled state
    host = jax.device_get(ss)
    y_ref, _ = dm.decode_step_reference(
        params, dm.DecodeState(*[jnp.asarray(a) for a in host]), x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-5)


def test_decode_engage_reasons_vocabulary():
    """The introspection satellite: every leg reports its resolved
    verdict — cmatmul vocabulary for the projections, plan verdicts
    for attention/spec/prefill, the active codec for kv_quant."""
    # overlap forced on: the verdict must not hang on the module-global
    # size registers other tests leave behind
    r = dm.decode_engage_reasons(8, 64, 8, 4, 128, tp=2, page=8,
                                 pages_max=2, spec_tokens=3, overlap=True)
    assert set(r) == {"qkv", "wo", "attention", "spec", "prefill",
                      "kv_quant"}
    assert r["attention"] == r["spec"] == r["prefill"] == "ok"
    assert r["kv_quant"] == "off"
    assert r["qkv"] is None          # every rung runs the kernels
    r = dm.decode_engage_reasons(7, 64, 8, 4, 128, tp=2, page=12,
                                 pages_max=2)
    assert r["qkv"] == "geometry" and r["attention"] == "geometry"
    r = dm.decode_engage_reasons(8, 64, 8, 4, 128, tp=2, page=8,
                                 pages_max=2, kv_dtype="int8")
    assert r["kv_quant"] == "int8"
    assert r["attention"] == "geometry"   # int8 wants page % 32


# ---------------------------------------------------------------------------
# quantization layer: at-rest codecs + churn
# ---------------------------------------------------------------------------

def test_kv_codec_storage_and_roundtrip(rng):
    """Codec plumbing: storage dtypes per mode, quantize/dequantize
    round trip within the fixed-scale tolerance, "off" bit-exact."""
    assert flash.kv_storage_dtype(jnp.float32, "off") == jnp.float32
    assert flash.kv_storage_dtype(jnp.float32, "bf16") == jnp.bfloat16
    assert flash.kv_storage_dtype(jnp.float32, "bf16_sr") == jnp.bfloat16
    assert flash.kv_storage_dtype(jnp.bfloat16, "int8") == jnp.int8
    x = _mk(rng, 4, 128)
    off = flash.quantize_kv(x, jnp.float32, mode="off")
    np.testing.assert_array_equal(np.asarray(off), np.asarray(x))
    q8 = flash.quantize_kv(x, jnp.int8, mode="int8")
    assert q8.dtype == jnp.int8
    back = flash.dequantize_kv(q8)
    tol = 0.5 / flash.get_kv_quant_scale()
    assert float(np.abs(np.asarray(back) - np.asarray(x)).max()) <= tol
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        flash.kv_storage_dtype(jnp.float32, "fp4")


@pytest.mark.parametrize("kv_dtype,page,tol", [
    ("bf16", 8, 2e-2), ("int8", 32, 4e-2)])
def test_quantized_churn_oracle_parity(rng, kv_dtype, page, tol):
    """The churn acceptance test: admission / retirement / growth
    against QUANTIZED page pools over a multi-step serving trace —
    per-step fp64-oracle parity within the codec tolerance, state
    invariants (lengths, disjoint tables, static shapes) exact."""
    flash.set_kv_cache_dtype(kv_dtype)
    try:
        params, state, mesh = _setup(rng, page=page, pmax=2,
                                     kv_dtype=kv_dtype)
        assert state.k_pages.dtype == flash.kv_storage_dtype(
            jnp.float32, kv_dtype)
        step = dm.build_decode_step(mesh)
        p_sh, _ = dm.shard_decode(params, state, mesh)
        state = dm.admit(state, 0)
        ref_state = state
        shapes = jax.tree_util.tree_map(lambda a: a.shape, state)
        schedule = {1: ("admit", 2), 3: ("retire", 0), 4: ("admit", 1)}
        for i in range(6):
            if i in schedule:
                op, slot = schedule[i]
                fn = dm.admit if op == "admit" else dm.retire
                state, ref_state = fn(state, slot), fn(ref_state, slot)
            x = _mk(rng, 4, 64)
            y, state = step(p_sh, state, x)
            y_ref, ref_state = dm.decode_step_reference(params,
                                                        ref_state, x)
            # oracle parity within codec tolerance (the unpaged
            # reference runs the same quantized pools, so this pins
            # paged-vs-unpaged agreement; the fp64 claim rides the
            # reference's dequantized math)
            np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                       rtol=tol, atol=tol)
            np.testing.assert_array_equal(np.asarray(state.seq_lens),
                                          np.asarray(ref_state.seq_lens))
            assert jax.tree_util.tree_map(lambda a: a.shape,
                                          state) == shapes
        assert list(np.asarray(state.seq_lens)) == [0, 2, 5, 0]
    finally:
        flash.set_kv_cache_dtype("off")


def test_quantized_pools_bit_exact_when_off(rng):
    """kv_cache_dtype="off" keeps every round-13 bit-exactness pin: the
    f32 churn trace matches the oracle to the old tolerances and the
    pools are bit-equal between sharded and reference steps."""
    params, state, mesh = _setup(rng)
    state = dm.admit(state, 0)
    step = dm.build_decode_step(mesh)
    p_sh, _ = dm.shard_decode(params, state, mesh)
    x = _mk(rng, 4, 64)
    y, s1 = step(p_sh, state, x)
    y_ref, s1_ref = dm.decode_step_reference(params, state, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s1.k_pages),
                               np.asarray(s1_ref.k_pages),
                               rtol=1e-6, atol=1e-6)


def test_quantized_spec_rollback_bit_exact(rng):
    """The rollback snapshot is captured in the POOL dtype, so
    accept/rollback stays bit-exact under the int8 codec too."""
    flash.set_kv_cache_dtype("int8")
    try:
        B, hkv, d, page, pmax, k = 2, 2, 128, 32, 2, 2
        kp = jnp.zeros((hkv, B * pmax, page, d), jnp.int8)
        vp = jnp.zeros_like(kp)
        bt = jnp.arange(B * pmax, dtype=jnp.int32).reshape(B, pmax)
        lens0 = jnp.asarray([3, 31], jnp.int32)
        # seed some history
        for _ in range(3):
            kn = _mk(rng, B, hkv, d)
            kp, vp, lens0 = flash.kv_cache_append(kp, vp, bt,
                                                  lens0 - 1, kn, kn)
        saved = flash.kv_cache_read_rows(kp, vp, bt, lens0, k)
        kn = _mk(rng, B, k, hkv, d)
        vn = _mk(rng, B, k, hkv, d)
        kp_m, vp_m, lens_m = flash.kv_cache_append_multi(
            kp, vp, bt, lens0, kn, vn)
        kp_r, vp_r, lens_r = flash.kv_cache_rollback(
            kp_m, vp_m, bt, lens_m, *saved,
            jnp.zeros((B,), jnp.int32), k)
        assert list(np.asarray(lens_r)) == list(np.asarray(lens0))
        np.testing.assert_array_equal(np.asarray(kp_r), np.asarray(kp))
        np.testing.assert_array_equal(np.asarray(vp_r), np.asarray(vp))
    finally:
        flash.set_kv_cache_dtype("off")


def test_serving_register_wiring(accl):
    """ACCLConfig round 18 registers write through to the kernel module
    on every assignment, and invalid values raise."""
    assert flash.get_flash_prefill_mode() == "paged"
    assert flash.get_kv_cache_dtype() == "off"
    base = accl.config
    try:
        accl.config = accl.config.replace(
            flash_prefill="unpaged", kv_cache_dtype="int8",
            kv_quant_scale=64.0, spec_decode_tokens=4)
        assert flash.get_flash_prefill_mode() == "unpaged"
        assert flash.get_kv_cache_dtype() == "int8"
        assert flash.get_kv_quant_scale() == 64.0
        assert accl.config.spec_decode_tokens == 4
    finally:
        accl.config = base
    assert flash.get_flash_prefill_mode() == "paged"
    assert flash.get_kv_cache_dtype() == "off"
    with pytest.raises(ValueError, match="flash_prefill"):
        flash.set_flash_prefill_mode("nope")
    with pytest.raises(ValueError, match="kv_quant_scale"):
        flash.set_kv_quant_scale(0.0)
    with pytest.raises(ValueError, match="prefill_mode"):
        flash.flash_prefill(
            jnp.zeros((8, 2, 128), jnp.float32),
            jnp.zeros((8, 1, 128), jnp.float32),
            jnp.zeros((8, 1, 128), jnp.float32),
            jnp.zeros((1, 2, 8, 128), jnp.float32),
            jnp.zeros((1, 2, 8, 128), jnp.float32),
            jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32),
            0, prefill_mode="bogus")
