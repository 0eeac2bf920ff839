"""LongCat-Flash at toy widths on the CPU (every ratio kept: a rotary part
half the no-position part, low ranks, a router of real and identity
outputs 2:1, two MLA sublayers a layer): the program through the serving
engine's paged latent cache against the plain reference
(`perfbench/reference/longcat_flash.py`, which imports nothing of the
program), and the pieces — absorbed against non-absorbed attention, the
kernel (interpreted) against the dense form, the expert layer's shares,
its bias, its edge cases and its two forms."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_operator_tpu.models.longcat import (LatentAttention, LongcatLM,
                                             rope_interleaved)
from mpi_operator_tpu.ops.attention import (mla_chains, mla_paged_attend,
                                            mla_paged_decode_attention,
                                            mla_pages_per_turn,
                                            mla_row_width, record_traced,
                                            traced_name)
from mpi_operator_tpu.parallel import held_experts as he
from mpi_operator_tpu.serve import EngineConfig, Request, ServingEngine
from mpi_operator_tpu.serve.transfer import PageTransfer
from mpi_operator_tpu.telemetry.worker import ServeTelemetry
from perfbench import weights_longcat as wl
from perfbench.kinds import _serve_longcat
from perfbench.reference import longcat_flash as ref

DIMS = wl.Dims(layers=2, hidden=96, heads=4, q_rank=24, kv_rank=8, nope=8,
               rope=4, v_dim=8, ffn=192, expert_ffn=32, experts_published=64,
               zero_experts=32, top_k=6, route_scale=6.0, rope_theta=1e7,
               eps=1e-5, held=(4, 4), vocab=128, std=0.02, bias_std=5e-4)
F32 = jnp.float32


def _model(dims=DIMS, max_len=64, **kw):
    model = _serve_longcat.model_of(dims, F32, max_len, False)
    return LongcatLM(dataclasses.replace(model.config, **kw))


def _params(seed=3, dims=DIMS):
    return jax.jit(lambda k: wl.make_params(k, dims, F32))(wl.seed_key(seed))


# -- the tree ------------------------------------------------------------

def test_the_benchmarks_tree_is_the_programs_tree_leaf_for_leaf():
    _serve_longcat.check_tree(_model(), DIMS, F32)
    wrong = dataclasses.replace(DIMS, kv_rank=16)
    with pytest.raises(RuntimeError, match="does not serve the tree"):
        _serve_longcat.check_tree(_model(), wrong, F32)


def test_param_count_at_the_published_widths_is_the_issues_arithmetic():
    import json
    import os
    cfg = json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(__file__)), "perfbench", "configs",
        "longcat-flash-1of32.json")))
    d = wl.Dims.from_config(cfg)
    assert d.held == (0, 16) and d.router_outputs == 768 and d.top_k == 12
    # 4 layers of 638.9 M outside the experts and 604.0 M in 16 of them,
    # and 201 M in the embedding and the head: 5.17 B, 10.35 GB in bf16
    assert abs(d.param_count() - 5.173e9) < 5e6


# -- the full forward pass -------------------------------------------------

def test_full_forward_matches_the_plain_reference_on_logits():
    params = _params()
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 128, (2, 24)))
    got = _model().apply({"params": params}, tokens)
    want = ref.forward(params, tokens, DIMS)
    assert float(jnp.abs(got - want).max()) < 2e-4
    assert float(jnp.abs(want).max()) > 0.5


@pytest.mark.parametrize("kernel", [False, True], ids=["dense", "kernel"])
def test_prefill_then_decode_through_the_latent_pages_matches_reference(
        kernel):
    """Prompts prefilled in chunks, then decoded a token at a time through
    the paged latent cache (absorbed attention; the kernel interpreted),
    against the reference's full forward pass over prompt and served
    tokens: on logits — the served token is the reference's best to within
    rounding, and its reported log-probability is the reference's."""
    params = _params()
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 128, n).tolist() for n in (7, 19, 12)]
    tel = ServeTelemetry()
    eng = ServingEngine(_model(), params, EngineConfig(
        slots=2, chunk_buckets=(4, 8), page_size=8, num_pages=24,
        decode_kernel=kernel), telemetry=tel)
    res = eng.run([Request(id=i, prompt=p, max_new_tokens=6 + i)
                   for i, p in enumerate(prompts)])
    assert eng.compile_counts()["step"] == 1
    assert eng.compile_counts()["prefill"] <= 2
    for i, p in enumerate(prompts):
        seq = p + res[i].tokens
        logits = ref.forward(params, jnp.asarray([seq]), DIMS)[0]
        logp = jax.nn.log_softmax(logits, -1)
        for j, tok in enumerate(res[i].tokens):
            at = len(p) - 1 + j
            assert float(logits[at].max() - logits[at, tok]) < 1e-4
            assert abs(float(logp[at, tok]) - res[i].logprobs[j]) < 1e-4
    # the routing counters came with the tokens, one observation a step
    steps = tel.decode_step_seconds.count
    assert {n: h.count for n, h in tel.step_counters.items()} == {
        n: steps for n in LongcatLM.STEP_COUNTERS}


def test_absorbed_attention_over_pages_equals_the_non_absorbed_form():
    """One MLA sublayer, the same weights and input: attention inside the
    window with K and V expanded, against one multi-token call through the
    latent pages with the up-projection absorbed."""
    cfg = _model().config
    dcfg = dataclasses.replace(cfg, decode=True, decode_page_size=8,
                               decode_num_pages=17)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, DIMS.hidden))
    plain = LatentAttention(cfg)
    p = plain.init(jax.random.PRNGKey(1), x)["params"]
    pages = jnp.asarray(np.random.RandomState(0).permutation(16)
                        .reshape(2, 8) + 1, jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(24)[None], (2, 24))
    got, cache = LatentAttention(dcfg).apply(
        {"params": p}, x, positions=pos, pages=pages, mutable=["cache"])
    want = plain.apply({"params": p}, x)
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(
        jnp.abs(want).max()) + 1e-6
    pool = cache["cache"]["latent"]
    assert pool.shape == (17, 8, mla_row_width(DIMS.kv_rank, DIMS.rope))
    assert float(jnp.abs(pool[..., DIMS.kv_rank + DIMS.rope:]).max()) == 0
    assert float(jnp.abs(pool[0]).max()) == 0       # nothing in the trash


def test_rope_rotates_interleaved_pairs_as_the_reference_does():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 9, 3, 8))
    pos = jnp.arange(9)[None]
    got = rope_interleaved(x, pos, 1e4)[0]
    assert float(jnp.abs(got - ref.rope(x[0], 1e4)).max()) < 1e-6
    # position 0 is left as it is; a pair keeps its length
    assert float(jnp.abs(got[0] - x[0, 0]).max()) == 0
    def pairs(a):
        return (a.reshape(a.shape[:-1] + (4, 2)) ** 2).sum(-1)
    assert float(jnp.abs(pairs(got) - pairs(x[0])).max()) < 1e-5


# -- the kernel ------------------------------------------------------------

def _latent_case(nblk, B=4, H=4, R=32, W=128, ps=16):
    """q, a pool of B * nblk pages behind the trash page, and a table
    that gives every row nblk pages of its own."""
    k = jax.random.split(jax.random.PRNGKey(nblk), 2)
    q = jax.random.normal(k[0], (B, H, W), F32)
    pool = jax.random.normal(k[1], (B * nblk + 1, ps, W), F32)
    pt = jnp.asarray(np.random.RandomState(nblk).permutation(B * nblk)
                     .reshape(B, nblk) + 1, jnp.int32)
    return q, pool, pt


def _brute_latent(q, pool, cur, pt, R, sm_scale):
    """Softmax over the gathered rows, nothing online, nothing paged."""
    B, nblk = pt.shape
    ps, W = pool.shape[1:]
    gathered = pool[pt].reshape(B, nblk * ps, W)
    s = jnp.einsum("bhw,bkw->bhk", q, gathered) * sm_scale
    s = jnp.where(jnp.arange(nblk * ps)[None, None] <= cur[:, None, None],
                  s, -1e30)
    return jnp.einsum("bhk,bkr->bhr", jax.nn.softmax(s, -1),
                      gathered[..., :R])


def _pages_a_turn(monkeypatch, pages, ps=16, W=128, chains=1):
    """Size the kernel's page slots for `pages` float32 pages a turn, and
    have the toy's four heads take a turn in `chains` chains (where the
    turn's pages divide so): the form that 128 heads take."""
    from mpi_operator_tpu.ops import attention
    monkeypatch.setattr(attention, "_MLA_PAGES_VMEM_BUDGET",
                        2 * pages * ps * W * 4)
    monkeypatch.setattr(attention, "_MLA_CHAINS", chains)
    monkeypatch.setattr(attention, "_MLA_CHAINS_HEADS", 4)


@pytest.mark.parametrize("chains", [1, 2])
@pytest.mark.parametrize("pages", [1, 2, 3, 100])
@pytest.mark.parametrize("nblk,cursors", [
    (4, [0, 15, 16, 63]),          # the first page, its end, the table's
    (22, [5, 31, 175, 351]),       # one page to the whole table
    (13, [207, 0, 17, 48]),        # a long row first, then short ones
    # a cursor at nblk * ps and beyond: a retiring row's post-EOS step
    # attends the whole table and reads nothing outside it
    (13, [208, 1000, 2 ** 30, 12]),
    # one either side of a page (16), of a turn of two pages (32), of
    # the pair of slots (64) and of two pairs (128)
    (13, [15, 16, 31, 32]),
    (13, [63, 64, 127, 128])])
def test_latent_decode_kernel_interpreted_matches_the_dense_form(
        monkeypatch, nblk, cursors, pages, chains):
    _pages_a_turn(monkeypatch, pages, chains=chains)
    q, pool, pt = _latent_case(nblk)
    cur = jnp.asarray(cursors, jnp.int32)
    got = mla_paged_decode_attention(q, pool, cur, pt, 32, 0.2)
    brute = _brute_latent(q, pool, jnp.minimum(cur, nblk * 16 - 1), pt, 32,
                          0.2)
    assert float(jnp.abs(got - brute).max()) < 1e-5
    inside = cur < nblk * 16        # the dense form skips rows past the cache
    want = mla_paged_attend(q[:, None], pool, cur[:, None], pt, 32, 0.2)[:, 0]
    assert float(jnp.abs(want - brute)[inside].max()) < 1e-5


@pytest.mark.parametrize("pages,chains", [(1, 1), (2, 1), (100, 1), (2, 2),
                                          (4, 4), (100, 2)])
def test_latent_decode_kernel_walks_a_free_row_beside_live_ones(
        monkeypatch, pages, chains):
    """A free slot's table is all trash and its cursor 0: it walks one
    page (the trash page) and disturbs neither neighbour — the rows
    before it fetched its first page for it."""
    _pages_a_turn(monkeypatch, pages, chains=chains)
    q, pool, pt = _latent_case(6)
    pt = pt.at[1].set(0).at[3].set(0)
    cur = jnp.asarray([70, 0, 33, 0], jnp.int32)
    got = mla_paged_decode_attention(q, pool, cur, pt, 32, 0.2)
    assert float(jnp.abs(got - _brute_latent(q, pool, cur, pt, 32, 0.2)
                         ).max()) < 1e-5


@pytest.mark.parametrize("pages,chains", [(1, 1), (2, 1), (3, 1), (100, 1),
                                          (2, 2), (4, 2), (4, 4)])
def test_latent_decode_kernel_reads_no_page_past_a_rows_cursor(
        monkeypatch, pages, chains):
    """Every table entry past a row's last live page points at a page
    full of NaN: a kernel that fetched one into a turn would carry it
    into the output through 0 x NaN. The call's last two turns fetch
    once more, unconditionally: the last row's first pages."""
    _pages_a_turn(monkeypatch, pages, chains=chains)
    nblk, ps = 9, 16
    q, pool, pt = _latent_case(nblk)
    cur = jnp.asarray([0, 16, 47, 143], jnp.int32)
    dead = jnp.arange(nblk)[None] > (cur // ps)[:, None]
    poison = pool.shape[0]
    poisoned = jnp.concatenate([pool, jnp.full((1,) + pool.shape[1:],
                                               jnp.nan, F32)])
    got = mla_paged_decode_attention(q, poisoned, cur,
                                     jnp.where(dead, poison, pt), 32, 0.2)
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got - _brute_latent(q, pool, cur, pt, 32, 0.2)
                         ).max()) < 1e-5


def test_latent_decode_kernel_sizes_a_turn_from_the_pages_bytes():
    # LongCat-Flash: bfloat16 pages of 64 rows of 640, a table of 100
    assert mla_pages_per_turn(100, 64 * 640 * 2) == 8
    assert mla_pages_per_turn(3, 64 * 640 * 2) == 3       # a short table
    assert mla_pages_per_turn(100, 1 << 30) == 1          # never none
    # and cuts a turn into chains from the head count, where it divides
    assert mla_chains(64, 8) == 1 and mla_chains(64, 16) == 1
    assert mla_chains(128, 16) == 2 and mla_chains(128, 8) == 2
    assert mla_chains(128, 3) == 1 and mla_chains(256, 16) == 2


@pytest.mark.parametrize("chains,starts,traced_as", [
    (1, 3, "pallas_mla_paged[live,pages=8]"),
    (2, 4, "pallas_mla_paged[live,pages=8,chains=2]"),
    (4, 6, "pallas_mla_paged[live,pages=8,chains=4]")])
def test_latent_decode_kernel_starts_a_turns_copies_in_a_loop(
        monkeypatch, chains, starts, traced_as):
    """However many pages a turn takes, the kernel's text holds two
    starts for the call's first row (its first two turns) and one a
    chain (a loop unrolled where it is lowered, not where it is traced),
    and three waits of a whole slot (a turn's, the last row's two): a
    descriptor a page in the text is paid for in every trace of a
    program that calls the kernel (PERF.md, PR 30)."""
    _pages_a_turn(monkeypatch, 8, chains=chains)
    q, pool, pt = _latent_case(13)
    with record_traced() as traced:
        text = str(jax.make_jaxpr(
            lambda *a: mla_paged_decode_attention(*a, 32, 0.2,
                                                  interpret=False))(
            q, pool, jnp.zeros(4, jnp.int32), pt))
    assert traced_name(traced["decode"]) == traced_as
    assert (text.count("dma_start"), text.count("dma_wait")) == (starts, 3)


@pytest.mark.parametrize("H,nblk,traced_as", [
    (128, 256, "pallas_mla_paged[live,pages=16,chains=2]"),   # DeepSeek-V2
    (64, 100, "pallas_mla_paged[live,pages=8]")])             # LongCat-Flash
def test_latent_decode_kernel_at_the_cells_shapes_matches_the_dense_form(
        H, nblk, traced_as):
    """The kernel as the two cells' programs hold it (bfloat16 pages of 64
    rows of 640, rank 512; interpreted) against `mla_paged_attend`: a
    cursor on a page's first and on its last position, a row of one live
    page, a row whose last turn is one page long, a row at the table's
    end and a cursor past the table."""
    ps, W, R = 64, 640, 512
    pages = mla_pages_per_turn(nblk, ps * W * 2)
    turn = pages * ps
    cursors = [3 * ps, 5 * ps - 1, 7, turn + 5, 2 * turn + ps - 1,
               nblk * ps - 1, 2 ** 30]
    B = len(cursors)
    k = jax.random.split(jax.random.PRNGKey(H), 2)
    q = jax.random.normal(k[0], (B, H, W), jnp.bfloat16)
    pool = jax.random.normal(k[1], (nblk + 1, ps, W), jnp.bfloat16)
    rng = np.random.RandomState(H)      # rows share the pool's pages
    pt = jnp.asarray(np.stack([rng.permutation(nblk) + 1
                               for _ in range(B)]), jnp.int32)
    cur = jnp.asarray(cursors, jnp.int32)
    with record_traced() as traced:
        got = mla_paged_decode_attention(q, pool, cur, pt, R, 0.1)
    assert traced_name(traced["decode"]) == traced_as
    want = mla_paged_attend(q[:, None], pool,
                            jnp.minimum(cur, nblk * ps - 1)[:, None], pt, R,
                            0.1)[:, 0]
    err = jnp.abs(got.astype(F32) - want.astype(F32)).max(axis=(1, 2))
    assert float(err.max()) <= 2e-2 * float(jnp.abs(want.astype(F32)).max()), err


def test_dense_form_walks_rows_in_groups_and_skips_rows_past_the_cache():
    """Many queries go through in row groups; a row whose positions lie
    past the logical cache (no member of a prefill call) neither extends
    the walk nor disturbs the others."""
    from mpi_operator_tpu.ops import attention
    B, S, H, R, W, ps, nblk = 4, 3, 2, 8, 128, 4, 5
    k = jax.random.split(jax.random.PRNGKey(0), 2)
    q = jax.random.normal(k[0], (B, S, H, W), F32)
    pool = jax.random.normal(k[1], (B * nblk + 1, ps, W), F32)
    pt = jnp.arange(B * nblk).reshape(B, nblk) + 1
    pos = jnp.asarray([[0, 1, 2], [9, 10, 11], [20, 20, 20], [4, 5, 6]])
    want = mla_paged_attend(q, pool, pos, pt, R, 0.3)
    old = attention._MLA_QUERY_ROWS
    attention._MLA_QUERY_ROWS = 2 * S * H          # groups of two rows
    try:
        got = mla_paged_attend(q, pool, pos, pt, R, 0.3)
    finally:
        attention._MLA_QUERY_ROWS = old
    assert float(jnp.abs(got - want).max()) < 1e-6
    away = pos.at[2].set(nblk * ps)
    rest = mla_paged_attend(q, pool, away, pt, R, 0.3)
    keep = jnp.asarray([0, 1, 3])
    assert float(jnp.abs(rest[keep] - want[keep]).max()) < 1e-6


# -- the expert layer --------------------------------------------------------

def _layer_inputs(seed=0, tokens=40, dims=DIMS):
    """A whole (uncut) expert layer's weights and some inputs."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    n, H, F = dims.experts_published, dims.hidden, dims.expert_ffn
    y = jax.random.normal(k[0], (tokens, H))
    p = {"router": 0.3 * jax.random.normal(k[1], (H, dims.router_outputs)),
         "bias": 5e-3 * jax.random.normal(k[2], (dims.router_outputs,)),
         "gate": 0.1 * jax.random.normal(k[3], (n, H, F)),
         "up": 0.1 * jax.random.normal(k[4], (n, H, F)),
         "down": 0.1 * jax.random.normal(k[5], (n, F, H))}
    return y, p


def _program_share(y, p, held, dims=DIMS):
    """What the program's layer gives a chip that holds `held`:
    (the held experts' part, the identity experts' part)."""
    lo, n = held
    logits = jnp.einsum("th,hn->tn", y, p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    idx, w = he.route(logits, p["bias"], dims.top_k, dims.route_scale)
    part = he.held_experts(y, idx, w, lo, p["gate"][lo:lo + n],
                           p["up"][lo:lo + n], p["down"][lo:lo + n])
    return part, he.identity_weight(idx, w, dims.experts_published)[:, None] \
        * y


def test_the_32_shares_and_what_is_counted_once_add_up_to_the_uncut_layer():
    """The shares test: the parts that all 32 chips' held experts give,
    with the identity experts' part counted once, are the uncut reference
    layer's result."""
    y, p = _layer_inputs()
    whole = dataclasses.replace(DIMS, held=(0, DIMS.experts_published))
    with jax.default_matmul_precision("highest"):
        want = ref.experts(p, y, whole, "f32")
        total = jnp.zeros_like(y)
        for share in range(32):
            part, identity = _program_share(y, p, (2 * share, 2))
            total = total + part
        got = total + identity
        # and the reference given one share is that share
        one = ref.experts({**p, **{k: p[k][6:8] for k in
                                   ("gate", "up", "down")}}, y,
                          dataclasses.replace(DIMS, held=(6, 2)), "f32")
        part, identity = _program_share(y, p, (6, 2))
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(
        jnp.abs(want).max())
    assert float(jnp.abs(part + identity - one).max()) < 1e-4 * float(
        jnp.abs(one).max())


def test_the_bias_moves_picks_but_not_weights():
    y, p = _layer_inputs()
    logits = y @ p["router"]
    prob = jax.nn.softmax(logits, -1)
    idx, w = he.route(logits, p["bias"], DIMS.top_k, DIMS.route_scale)
    idx0, _ = he.route(logits, jnp.zeros_like(p["bias"]), DIMS.top_k,
                       DIMS.route_scale)
    assert not np.array_equal(np.sort(idx, -1), np.sort(idx0, -1))
    # a weight is 6 p of the pick, whatever the bias; not renormalised
    np.testing.assert_allclose(
        w, DIMS.route_scale * jnp.take_along_axis(prob, idx, -1), rtol=1e-6)
    assert float(jnp.abs(w.sum(-1) - DIMS.route_scale).min()) > 1e-3
    # the picks are the top of p + b
    top = jnp.sort(prob + p["bias"], -1)[:, -DIMS.top_k:]
    np.testing.assert_allclose(
        jnp.sort(jnp.take_along_axis(prob + p["bias"], idx, -1), -1), top,
        rtol=1e-6)


@pytest.mark.parametrize("where", ["identity", "absent", "held"])
def test_a_token_whose_picks_all_fall_in_one_place(where):
    """All twelve picks on identity experts: M(y) = (sum of weights) y and
    no held expert runs for it; all on absent experts: M(y) = 0 here; all
    on held ones: every pick is computed."""
    y, p = _layer_inputs(tokens=3)
    k, n = DIMS.top_k, DIMS.experts_published
    place = {"identity": n, "absent": 20, "held": 4}[where]
    held = (4, k)
    bias = jnp.full((DIMS.router_outputs,), -1.0).at[
        place:place + k].set(1.0)
    pp = {**p, "bias": bias}
    part, identity = _program_share(y, pp, held)
    logits = y @ p["router"]
    idx, w = he.route(logits, bias, k, DIMS.route_scale)
    assert np.array_equal(np.sort(idx, -1),
                          np.broadcast_to(np.arange(place, place + k),
                                          (3, k)))
    counts = [int(c) for c in he.pick_counts(idx, held[0], held[1], n)]
    if where == "identity":
        assert float(jnp.abs(part).max()) == 0
        np.testing.assert_allclose(identity, w.sum(-1, keepdims=True) * y,
                                   rtol=1e-6)
        assert counts == [0, 3 * k, 0]
    elif where == "absent":
        assert float(jnp.abs(part).max()) == 0
        assert float(jnp.abs(identity).max()) == 0
        assert counts == [0, 0, 0]
    else:
        want = ref.experts(
            {**pp, **{m: p[m][4:4 + k] for m in ("gate", "up", "down")}}, y,
            dataclasses.replace(DIMS, held=held), "f32")
        np.testing.assert_allclose(part, want, rtol=2e-4, atol=1e-5)
        assert counts == [3 * k, 0, 3]


@pytest.mark.parametrize("tokens,block_rows", [(40, 8), (300, 16), (5, 4)])
def test_masked_and_grouped_forms_give_the_same_and_drop_nothing(
        tokens, block_rows):
    y, p = _layer_inputs(seed=tokens, tokens=tokens)
    lo, n = 10, 12
    logits = y @ p["router"]
    idx, w = he.route(logits, p["bias"], DIMS.top_k, DIMS.route_scale)
    g, u, d = (p[m][lo:lo + n] for m in ("gate", "up", "down"))
    masked = he.masked_experts(y, he.held_gates(idx, w, lo, n), g, u, d)
    grouped = he.grouped_experts(y, idx, w, lo, g, u, d,
                                 block_rows=block_rows)
    scale = float(jnp.abs(masked).max())
    assert scale > 0
    assert float(jnp.abs(masked - grouped).max()) < 1e-5 * scale
    # dropless: every assignment to a held expert is in the result
    want = jnp.zeros_like(y)
    for t in range(tokens):
        for j in range(DIMS.top_k):
            e = int(idx[t, j]) - lo
            if 0 <= e < n:
                h = jax.nn.silu(y[t] @ g[e]) * (y[t] @ u[e])
                want = want.at[t].add(w[t, j] * (h @ d[e]))
    assert float(jnp.abs(masked - want).max()) < 1e-4 * scale


def test_which_form_runs_follows_the_tokens_in_the_call(monkeypatch):
    y, p = _layer_inputs(tokens=he.MASKED_MAX_TOKENS + 1)
    idx, w = he.route(y @ p["router"], p["bias"], DIMS.top_k, 6.0)
    seen = []
    monkeypatch.setattr(he, "masked_experts",
                        lambda *a: seen.append("masked") or 0)
    monkeypatch.setattr(he, "grouped_experts",
                        lambda *a: seen.append("grouped") or 0)
    he.held_experts(y, idx, w, 0, p["gate"][:2], p["up"][:2], p["down"][:2])
    he.held_experts(y[:-1], idx[:-1], w[:-1], 0, p["gate"][:2], p["up"][:2],
                    p["down"][:2])
    assert seen == ["grouped", "masked"]


def test_an_expert_layer_told_a_range_it_holds_no_weights_for_says_so():
    model = _model(held=(4, 6))
    with pytest.raises(Exception, match="has shape"):
        model.apply({"params": _params()}, jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(ValueError, match="not a range"):
        _model(held=(62, 4)).init(jax.random.PRNGKey(0),
                                  jnp.zeros((1, 4), jnp.int32))


def test_step_counters_are_the_steps_own_routing():
    """What the engine fetches with a step's tokens is what the router
    picked in that step, summed over the layers."""
    params = _params()
    dmodel = _model(decode=True, decode_page_size=8, decode_num_pages=9)
    tokens = jnp.asarray([[5], [9]])
    pages = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]] * 2, jnp.int32)
    _, vars_ = dmodel.apply({"params": params}, tokens,
                            positions=jnp.zeros((2, 1), jnp.int32),
                            with_head=False, pages=pages,
                            mutable=["cache", "counters"])
    got = sum(jax.tree.leaves(vars_["counters"]))
    assert got.shape == (3,) and len(jax.tree.leaves(
        vars_["counters"])) == DIMS.layers
    held, identity, load = (int(x) for x in got)
    assert 0 <= held <= 2 * DIMS.top_k * DIMS.layers
    assert 0 < identity <= 2 * DIMS.top_k * DIMS.layers
    assert load <= held and (load > 0) == (held > 0)


# -- the engine around a second kind of cache -------------------------------

def test_page_bytes_follow_the_kind_of_cache():
    from mpi_operator_tpu.models.transformer import create_lm
    gpt = create_lm("gpt2-test", dtype=F32)
    gparams = gpt.init(jax.random.PRNGKey(0),
                       jnp.zeros((1, 4), jnp.int32))["params"]
    g = gpt.config
    eng = ServingEngine(gpt, gparams, EngineConfig(
        slots=2, chunk_buckets=(8,), page_size=8, num_pages=5))
    assert eng.page_bytes() == (2 * g.num_layers * g.kv_heads * g.head_dim
                                * 8 * 4)
    lat = ServingEngine(_model(), _params(), EngineConfig(
        slots=2, chunk_buckets=(8,), page_size=8, num_pages=5))
    assert lat.page_bytes() == 2 * DIMS.layers * 128 * 8 * 4


def test_the_latent_cache_is_paged_or_says_why_not():
    """At the model: a decode-mode `LongcatLM` applied without a page
    size (as `generate()` would) says why it cannot."""
    with pytest.raises(ValueError, match="latent cache is a page pool"):
        _model(decode=True).apply({"params": _params()},
                                  jnp.zeros((1, 4), jnp.int32),
                                  positions=jnp.arange(4)[None])


def test_own_params_serves_a_tree_in_the_served_type_where_it_lies():
    params = _params()
    eng = ServingEngine(_model(), params, EngineConfig(
        slots=2, chunk_buckets=(8,), page_size=8, num_pages=9,
        own_params=True))
    assert eng.params["embedding"] is params["embedding"]
    copy = ServingEngine(_model(), params, EngineConfig(
        slots=2, chunk_buckets=(8,), page_size=8, num_pages=9))
    assert copy.params["embedding"] is not params["embedding"]
    # a tree not yet in the served type is cast, owned or not
    half = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    cast = ServingEngine(_model(), half, EngineConfig(
        slots=2, chunk_buckets=(8,), page_size=8, num_pages=9,
        own_params=True))
    assert cast.params["embedding"].dtype == F32


def test_page_transfer_moves_latent_pages():
    """`PageTransfer` moves whatever has the pool's page count on its
    first axis: one latent row a position moves as K and V a head do."""
    def pools(n):
        return {f"layer_{i}": {f"attn_{j}": {"latent": jnp.zeros((n, 8, 128))}
                               for j in (0, 1)} for i in range(2)}
    src = jax.tree.map(
        lambda x: jax.random.normal(jax.random.PRNGKey(0), x.shape), pools(9))
    tr = PageTransfer(9, 7)
    dst, moved = tr.move(src, pools(7), [3, 5, 8], [1, 2, 6])
    assert moved == 3 and tr.pages_moved == 3
    for a, b in zip(jax.tree.leaves(src), jax.tree.leaves(dst)):
        np.testing.assert_array_equal(b[jnp.asarray([1, 2, 6])],
                                      a[jnp.asarray([3, 5, 8])])
        assert float(jnp.abs(b[jnp.asarray([3, 4, 5])]).max()) == 0


def test_decode_step_scopes_name_the_steps_instructions():
    eng = ServingEngine(_model(), _params(), EngineConfig(
        slots=2, chunk_buckets=(8,), page_size=8, num_pages=9))
    scopes = eng.decode_step_scopes()
    assert scopes and all(isinstance(v, str) for v in scopes.values())
    joined = " ".join(scopes.values())
    assert "mla." in joined and "moe." in joined
