"""Parallelism-strategy tests on the 8-virtual-device CPU mesh.

The reference tests multi-node behavior declaratively (SURVEY.md §4); we own
a data plane, so every strategy is verified numerically against its dense /
sequential reference: TP+FSDP (sharded == replicated forward), SP (ring ==
dense attention), EP (sharded MoE == single-device MoE), PP (pipeline ==
sequential stages) — forward AND backward.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta
from jax.sharding import NamedSharding, PartitionSpec as P

from mpi_operator_tpu.models.transformer import (
    CausalLM, dense_attention, gpt2_config)
from mpi_operator_tpu.parallel import (
    MeshConfig, MoeMlp, make_mesh, pipeline_apply, ring_attention,
    shard_init, stack_stage_params)


# ---------------------------------------------------------------------------
# tensor parallel + fsdp
# ---------------------------------------------------------------------------

class TestTensorParallel:
    def test_sharded_forward_matches_replicated(self):
        cfg = gpt2_config("test", attention="dense", dtype=jnp.float32,
                          vocab_size=512, max_len=64)
        model = CausalLM(cfg)
        toks = jax.random.randint(jax.random.PRNGKey(0), (4, 32), 0, 512)
        vs_ref = meta.unbox(model.init(jax.random.PRNGKey(7), toks))
        ref = model.apply(vs_ref, toks)

        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
        vs, shardings = shard_init(model, mesh, jax.random.PRNGKey(7), toks)
        toks_sh = jax.device_put(
            toks, NamedSharding(mesh, P(("dcn", "dp", "fsdp"))))
        out = jax.jit(model.apply)(vs, toks_sh)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   atol=2e-5)

    def test_params_actually_sharded(self):
        cfg = gpt2_config("test", attention="dense", dtype=jnp.float32,
                          vocab_size=512, max_len=64)
        model = CausalLM(cfg)
        toks = jnp.zeros((2, 16), jnp.int32)
        mesh = make_mesh(MeshConfig(tp=8))
        vs, shardings = shard_init(model, mesh, jax.random.PRNGKey(0), toks)
        # the FFN in-projection must be tp-sharded on its mlp dim
        k = vs["params"]["backbone"]["block_0"]["mlp"]["fc_in"]["kernel"]
        spec = k.sharding.spec
        assert "tp" in jax.tree.leaves(tuple(spec)), spec
        # local shard is 1/8th of the full mlp dim
        assert k.addressable_shards[0].data.shape[-1] == k.shape[-1] // 8


# ---------------------------------------------------------------------------
# sequence parallel (ring attention)
# ---------------------------------------------------------------------------

class TestRingAttention:
    @pytest.mark.parametrize("impl", ["dense", "flash"])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, causal, impl):
        mesh = make_mesh(MeshConfig(dp=2, sp=4))
        B, S, H, D = 4, 64, 2, 16
        q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (B, S, H, D))
                   for i in range(3))
        ref = dense_attention(q, k, v, causal=causal, dtype=jnp.float32)
        out = ring_attention(q, k, v, mesh, causal=causal, impl=impl)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   atol=1e-5)

    @pytest.mark.parametrize("impl", ["dense", "flash"])
    @pytest.mark.parametrize("causal", [True, False])
    def test_gradients_match_dense(self, causal, impl):
        mesh = make_mesh(MeshConfig(sp=8))
        B, S, H, D = 2, 32, 2, 8
        q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (B, S, H, D))
                   for i in range(3))

        def lr(q, k, v):
            return (ring_attention(q, k, v, mesh, causal=causal,
                                   impl=impl) ** 2).sum()

        def ld(q, k, v):
            return (dense_attention(q, k, v, causal=causal,
                                    dtype=jnp.float32) ** 2).sum()

        g1 = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(ld, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4)


# ---------------------------------------------------------------------------
# expert parallel (MoE)
# ---------------------------------------------------------------------------

class TestMoE:
    def _model_and_input(self):
        m = MoeMlp(num_experts=4, embed_dim=32, mlp_dim=64, top_k=2,
                   capacity_factor=2.0, dtype=jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32))
        vs = meta.unbox(m.init(jax.random.PRNGKey(1), x))
        return m, x, vs

    def test_forward_and_aux(self):
        m, x, vs = self._model_and_input()
        out, aux = m.apply(vs, x)
        assert out.shape == x.shape
        assert float(aux) >= 1.0 - 1e-5     # aux >= 1 at/above uniform load

    def test_ep_sharded_matches_dense(self):
        m, x, vs = self._model_and_input()
        out, _ = m.apply(vs, x)
        mesh = make_mesh(MeshConfig(dp=2, ep=4))
        from mpi_operator_tpu.parallel.sharding import param_shardings
        abstract = jax.eval_shape(lambda r: m.init(r, x),
                                  jax.random.PRNGKey(1))
        sh = param_shardings(mesh, abstract)
        out_sh = jax.tree.unflatten(
            jax.tree.structure(meta.unbox(abstract)), jax.tree.leaves(sh))
        vs_sharded = jax.jit(lambda v: v, out_shardings=out_sh)(vs)
        xs = jax.device_put(x, NamedSharding(mesh, P(("dp",))))
        out2, _ = jax.jit(m.apply)(vs_sharded, xs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(out2),
                                   atol=1e-5)

    def test_capacity_drops_tokens(self):
        """With capacity 1 token/expert, most tokens are dropped — output
        stays finite and partially zero."""
        m = MoeMlp(num_experts=2, embed_dim=8, mlp_dim=16, top_k=1,
                   capacity_factor=0.01, dtype=jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 8))
        vs = meta.unbox(m.init(jax.random.PRNGKey(1), x))
        out, _ = m.apply(vs, x)
        assert bool(jnp.isfinite(out).all())
        row_norms = jnp.abs(out[0]).sum(-1)
        assert int((row_norms == 0).sum()) >= 16   # dropped rows contribute 0

    def test_grads_finite(self):
        m, x, vs = self._model_and_input()

        def loss(p):
            out, aux = m.apply(p, x)
            return (out ** 2).mean() + 0.01 * aux

        grads = jax.grad(loss)(vs)
        for g in jax.tree.leaves(grads):
            assert bool(jnp.isfinite(g).all())


# ---------------------------------------------------------------------------
# pipeline parallel
# ---------------------------------------------------------------------------

class TestPipeline:
    def _setup(self):
        mesh = make_mesh(MeshConfig(dp=2, pp=4))
        E = 16
        per_stage = [
            {"w": jax.random.normal(jax.random.PRNGKey(i), (E, E))
             / np.sqrt(E), "b": jnp.zeros((E,))} for i in range(4)]
        stacked = stack_stage_params(per_stage)

        def stage_fn(p, x):
            return jnp.tanh(x @ p["w"][0] + p["b"][0])

        x = jax.random.normal(jax.random.PRNGKey(99), (8, 4, E))
        return mesh, per_stage, stacked, stage_fn, x

    def _sequential(self, per_stage, x):
        h = x
        for p in per_stage:
            h = jnp.tanh(h @ p["w"] + p["b"])
        return h

    def test_forward_matches_sequential(self):
        mesh, per_stage, stacked, stage_fn, x = self._setup()
        out = pipeline_apply(stage_fn, stacked, x, mesh, num_microbatches=8)
        ref = self._sequential(per_stage, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-6)

    def test_backward_matches_sequential(self):
        mesh, per_stage, stacked, stage_fn, x = self._setup()

        def loss_pipe(params):
            return (pipeline_apply(stage_fn, params, x, mesh,
                                   num_microbatches=8) ** 2).sum()

        def loss_seq(per):
            return (self._sequential(per, x) ** 2).sum()

        g1 = jax.grad(loss_pipe)(stacked)
        g2 = stack_stage_params(jax.grad(loss_seq)(per_stage))
        np.testing.assert_allclose(np.asarray(g1["w"]), np.asarray(g2["w"]),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(g1["b"]), np.asarray(g2["b"]),
                                   atol=1e-5)


class TestPipelineLM:
    """VERDICT #6: the pipeline must carry an actual transformer, not a
    toy layer — loss and grads of the stage-sliced CausalLM must match the
    unpiped model on identical parameters."""

    def _setup(self):
        from mpi_operator_tpu.parallel import pipeline_lm_loss, stack_lm_params
        from mpi_operator_tpu.train.lm_trainer import lm_loss

        cfg = gpt2_config("test", attention="dense", dtype=jnp.float32,
                          vocab_size=256, max_len=32)      # 2 layers
        model = CausalLM(cfg)
        B, S, M = 8, 16, 4
        key = jax.random.PRNGKey(3)
        toks = jax.random.randint(key, (B, S + 1), 0, cfg.vocab_size)
        toks, tgts = toks[:, :-1], toks[:, 1:]
        vs = meta.unbox(model.init(jax.random.PRNGKey(7), toks))
        mesh = make_mesh(MeshConfig(pp=2, dp=4))
        pp_params = stack_lm_params(vs["params"], cfg.num_layers)
        mb = (toks.reshape(M, B // M, S), tgts.reshape(M, B // M, S))
        return (cfg, model, vs, toks, tgts, mesh, pp_params, mb, M,
                pipeline_lm_loss, stack_lm_params, lm_loss)

    def test_loss_matches_unpiped(self):
        (cfg, model, vs, toks, tgts, mesh, pp_params, (tk, tg), M,
         pipeline_lm_loss, _, lm_loss) = self._setup()
        ref = lm_loss(model.apply(vs, toks), tgts)
        out = jax.jit(lambda p: pipeline_lm_loss(
            cfg, p, tk, tg, mesh, M))(pp_params)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   atol=1e-5)

    def test_grads_match_unpiped(self):
        (cfg, model, vs, toks, tgts, mesh, pp_params, (tk, tg), M,
         pipeline_lm_loss, stack_lm_params, lm_loss) = self._setup()

        g_pipe = jax.jit(jax.grad(lambda p: pipeline_lm_loss(
            cfg, p, tk, tg, mesh, M)))(pp_params)
        g_ref = jax.grad(lambda p: lm_loss(
            model.apply({"params": p}, toks), tgts))(vs["params"])
        g_ref = stack_lm_params(g_ref, cfg.num_layers)
        flat_p, _ = jax.tree_util.tree_flatten_with_path(g_pipe)
        flat_r = jax.tree.leaves(g_ref)
        assert len(flat_p) == len(flat_r)
        for (path, a), b in zip(flat_p, flat_r):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-4,
                err_msg=jax.tree_util.keystr(path))

    def test_dp_sharded_stream_matches_unpiped(self):
        """pp×dp with the microbatch dim actually SHARDED over dp (mb
        divisible by the data degree): each dp rank pipelines its own
        slice and the psum spans pp+dp — loss and grads must still match
        the unpiped model exactly."""
        from mpi_operator_tpu.parallel import pipeline_lm_loss, stack_lm_params
        from mpi_operator_tpu.train.lm_trainer import lm_loss

        cfg = gpt2_config("test", attention="dense", dtype=jnp.float32,
                          vocab_size=256, max_len=32)
        model = CausalLM(cfg)
        B, S, M = 16, 16, 4                   # mb=4 divides dp=4 → sharded
        toks = jax.random.randint(jax.random.PRNGKey(3), (B, S + 1), 0,
                                  cfg.vocab_size)
        toks, tgts = toks[:, :-1], toks[:, 1:]
        vs = meta.unbox(model.init(jax.random.PRNGKey(7), toks))
        mesh = make_mesh(MeshConfig(pp=2, dp=4))
        pp_params = stack_lm_params(vs["params"], cfg.num_layers)
        tk, tg = toks.reshape(M, B // M, S), tgts.reshape(M, B // M, S)

        ref = lm_loss(model.apply(vs, toks), tgts)
        out = jax.jit(lambda p: pipeline_lm_loss(
            cfg, p, tk, tg, mesh, M))(pp_params)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   atol=1e-5)
        g_pipe = jax.jit(jax.grad(lambda p: pipeline_lm_loss(
            cfg, p, tk, tg, mesh, M)))(pp_params)
        g_ref = stack_lm_params(
            jax.grad(lambda p: lm_loss(
                model.apply({"params": p}, toks), tgts))(vs["params"]),
            cfg.num_layers)
        for a, b in zip(jax.tree.leaves(g_pipe), jax.tree.leaves(g_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4)

    def test_pp_sp_ring_stream_matches_unpiped(self):
        """pp×sp composition: the stream's sequence dim sharded over sp,
        each stage tick ringing its attention over the sp neighbors
        (cfg.attention='ring' + positions offset per shard) — loss AND
        grads must match the unpiped dense model on identical params."""
        import dataclasses

        from mpi_operator_tpu.parallel import pipeline_lm_loss, stack_lm_params
        from mpi_operator_tpu.train.lm_trainer import lm_loss

        cfg_ring = gpt2_config("test", attention="ring", dtype=jnp.float32,
                               vocab_size=256, max_len=32)
        cfg_dense = dataclasses.replace(cfg_ring, attention="dense")
        model = CausalLM(cfg_dense)
        B, S, M = 8, 16, 4
        toks = jax.random.randint(jax.random.PRNGKey(3), (B, S + 1), 0,
                                  cfg_ring.vocab_size)
        toks, tgts = toks[:, :-1], toks[:, 1:]
        vs = meta.unbox(model.init(jax.random.PRNGKey(7), toks))
        mesh = make_mesh(MeshConfig(pp=2, sp=2, dp=2))
        pp_params = stack_lm_params(vs["params"], cfg_ring.num_layers)
        tk, tg = toks.reshape(M, B // M, S), tgts.reshape(M, B // M, S)

        ref = lm_loss(model.apply(vs, toks), tgts)
        out = jax.jit(lambda p: pipeline_lm_loss(
            cfg_ring, p, tk, tg, mesh, M))(pp_params)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   atol=2e-5)
        g_pipe = jax.jit(jax.grad(lambda p: pipeline_lm_loss(
            cfg_ring, p, tk, tg, mesh, M)))(pp_params)
        g_ref = stack_lm_params(
            jax.grad(lambda p: lm_loss(
                model.apply({"params": p}, toks), tgts))(vs["params"]),
            cfg_ring.num_layers)
        flat_p, _ = jax.tree_util.tree_flatten_with_path(g_pipe)
        for (path, a), b in zip(flat_p, jax.tree.leaves(g_ref)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=3e-4,
                err_msg=jax.tree_util.keystr(path))

    def test_pp_sp_rejects_non_ring_attention(self):
        """A dense/flash stage body under sp would attend within its own
        S/sp shard only — silently truncated context. Rejected loudly."""
        from mpi_operator_tpu.parallel import pipeline_lm_loss, stack_lm_params

        cfg = gpt2_config("test", attention="dense", dtype=jnp.float32,
                          vocab_size=256, max_len=32)
        model = CausalLM(cfg)
        vs = meta.unbox(model.init(jax.random.PRNGKey(0),
                                   jnp.zeros((2, 16), jnp.int32)))
        pp_params = stack_lm_params(vs["params"], cfg.num_layers)
        mesh = make_mesh(MeshConfig(pp=2, sp=2, dp=2))
        tk = jnp.zeros((4, 2, 16), jnp.int32)
        with pytest.raises(ValueError, match="ring"):
            pipeline_lm_loss(cfg, pp_params, tk, tk, mesh, 4)

    def test_masked_lm_pipeline_matches_unpiped(self):
        """The pipelined MaskedLM (BERT family): mask stream riding the
        relays, MLM transform head on the last stage, dynamic mask-count
        divisor — loss AND grads must match the unpiped MaskedLM +
        lm_loss(mask) on identical params."""
        from mpi_operator_tpu.models.transformer import (MaskedLM,
                                                         bert_config)
        from mpi_operator_tpu.parallel import (pipeline_mlm_loss,
                                               stack_mlm_params)
        from mpi_operator_tpu.train.lm_trainer import lm_loss

        cfg = bert_config("test", attention="dense", dtype=jnp.float32,
                          vocab_size=256, max_len=32)      # 2 layers
        model = MaskedLM(cfg)
        B, S, M = 8, 16, 4
        key = jax.random.PRNGKey(3)
        orig = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
        mask = (jax.random.uniform(jax.random.PRNGKey(5), (B, S))
                < 0.25).astype(jnp.float32)
        toks = jnp.where(mask > 0, cfg.vocab_size - 1, orig)
        vs = meta.unbox(model.init(jax.random.PRNGKey(7), toks))
        mesh = make_mesh(MeshConfig(pp=2, dp=4))
        pp_params = stack_mlm_params(vs["params"], cfg.num_layers)
        tk = toks.reshape(M, B // M, S)
        tg = orig.reshape(M, B // M, S)
        mk = mask.reshape(M, B // M, S)

        ref = lm_loss(model.apply(vs, toks), orig, mask)
        out = jax.jit(lambda p: pipeline_mlm_loss(
            cfg, p, tk, tg, mk, mesh, M))(pp_params)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   atol=2e-5)
        g_pipe = jax.jit(jax.grad(lambda p: pipeline_mlm_loss(
            cfg, p, tk, tg, mk, mesh, M)))(pp_params)
        g_ref = stack_mlm_params(
            jax.grad(lambda p: lm_loss(
                model.apply({"params": p}, toks), orig, mask))(
                vs["params"]),
            cfg.num_layers)
        flat_p, _ = jax.tree_util.tree_flatten_with_path(g_pipe)
        flat_r = jax.tree_util.tree_flatten_with_path(g_ref)[0]
        assert [p for p, _ in flat_p] == [p for p, _ in flat_r]
        for (path, a), (_, b) in zip(flat_p, flat_r):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=3e-4,
                err_msg=jax.tree_util.keystr(path))

    def test_pp_fused_xent_matches_unfused(self):
        """--fused-xent with --pp (VERDICT r04 next #7): the chunked
        tied-head loss on the last stage must equal the unfused pp loss
        and grads exactly — GPipe and 1F1B."""
        from mpi_operator_tpu.parallel import pipeline_lm_loss, stack_lm_params
        from mpi_operator_tpu.parallel.pipeline_1f1b import (
            pipeline_lm_1f1b_grads)

        cfg = gpt2_config("test", attention="dense", dtype=jnp.float32,
                          vocab_size=256, max_len=32)
        model = CausalLM(cfg)
        B, S, M = 8, 16, 4
        toks = jax.random.randint(jax.random.PRNGKey(3), (B, S + 1), 0,
                                  cfg.vocab_size)
        toks, tgts = toks[:, :-1], toks[:, 1:]
        vs = meta.unbox(model.init(jax.random.PRNGKey(7), toks))
        mesh = make_mesh(MeshConfig(pp=2, dp=4))
        pp_params = stack_lm_params(vs["params"], cfg.num_layers)
        tk, tg = toks.reshape(M, B // M, S), tgts.reshape(M, B // M, S)

        l0, g0 = jax.jit(jax.value_and_grad(lambda p: pipeline_lm_loss(
            cfg, p, tk, tg, mesh, M)))(pp_params)
        l1, g1 = jax.jit(jax.value_and_grad(lambda p: pipeline_lm_loss(
            cfg, p, tk, tg, mesh, M, fused_xent=True)))(pp_params)
        np.testing.assert_allclose(np.asarray(l0), np.asarray(l1),
                                   atol=2e-5)
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4)
        lf, gf = jax.jit(lambda p: pipeline_lm_1f1b_grads(
            cfg, p, tk, tg, mesh, M, fused_xent=True))(pp_params)
        np.testing.assert_allclose(np.asarray(l0), np.asarray(lf),
                                   atol=2e-5)
        for a, b in zip(jax.tree.leaves(g0["blocks"]),
                        jax.tree.leaves(gf["blocks"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4)

    def test_masked_pp_sp_ring_matches_unpiped(self):
        """pp×sp for the MASKED (BERT) pipeline (advisor r04): the
        bidirectional ring-attention stage body under the pipeline with
        the sp-sharded mask stream — loss AND grads must match the
        unpiped dense MaskedLM on identical params (the causal pp×sp and
        masked pp×dp combinations each had this pin; the composition now
        does too)."""
        import dataclasses

        from mpi_operator_tpu.models.transformer import (MaskedLM,
                                                         bert_config)
        from mpi_operator_tpu.parallel import (pipeline_mlm_loss,
                                               stack_mlm_params)
        from mpi_operator_tpu.train.lm_trainer import lm_loss

        cfg_ring = bert_config("test", attention="ring", dtype=jnp.float32,
                               vocab_size=256, max_len=32)
        model = MaskedLM(dataclasses.replace(cfg_ring, attention="dense"))
        B, S, M = 8, 32, 4
        orig = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0,
                                  cfg_ring.vocab_size)
        mask = (jax.random.uniform(jax.random.PRNGKey(5), (B, S))
                < 0.25).astype(jnp.float32)
        toks = jnp.where(mask > 0, cfg_ring.vocab_size - 1, orig)
        vs = meta.unbox(model.init(jax.random.PRNGKey(7), toks))
        mesh = make_mesh(MeshConfig(pp=2, sp=2, dp=2))
        pp_params = stack_mlm_params(vs["params"], cfg_ring.num_layers)
        tk = toks.reshape(M, B // M, S)
        tg = orig.reshape(M, B // M, S)
        mk = mask.reshape(M, B // M, S)

        ref = lm_loss(model.apply(vs, toks), orig, mask)
        out = jax.jit(lambda p: pipeline_mlm_loss(
            cfg_ring, p, tk, tg, mk, mesh, M))(pp_params)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   atol=2e-5)
        g_pipe = jax.jit(jax.grad(lambda p: pipeline_mlm_loss(
            cfg_ring, p, tk, tg, mk, mesh, M)))(pp_params)
        g_ref = stack_mlm_params(
            jax.grad(lambda p: lm_loss(
                model.apply({"params": p}, toks), orig, mask))(
                vs["params"]),
            cfg_ring.num_layers)
        flat_p, _ = jax.tree_util.tree_flatten_with_path(g_pipe)
        flat_r = jax.tree_util.tree_flatten_with_path(g_ref)[0]
        assert [p for p, _ in flat_p] == [p for p, _ in flat_r]
        for (path, a), (_, b) in zip(flat_p, flat_r):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=3e-4,
                err_msg=jax.tree_util.keystr(path))

    def _moe_setup(self, dropless):
        """4-layer GPT-2 test config with MoE every 2nd block (blocks 1,3)
        — pp=2 stages each own one (dense, MoE) period."""
        from mpi_operator_tpu.parallel import (pipeline_lm_loss,
                                               stack_lm_params)
        from mpi_operator_tpu.train.lm_trainer import lm_loss

        cfg = gpt2_config("test", attention="dense", dtype=jnp.float32,
                          vocab_size=256, max_len=32, num_layers=4,
                          num_experts=4, moe_every=2,
                          moe_dropless=dropless)
        model = CausalLM(cfg)
        B, S, M = 8, 16, 4
        toks = jax.random.randint(jax.random.PRNGKey(3), (B, S + 1), 0,
                                  cfg.vocab_size)
        toks, tgts = toks[:, :-1], toks[:, 1:]
        vs = meta.unbox(model.init(jax.random.PRNGKey(7), toks))
        pp_params = stack_lm_params(vs["params"], cfg.num_layers,
                                    num_experts=cfg.num_experts,
                                    moe_every=cfg.moe_every)
        assert "moe" in pp_params
        tk, tg = toks.reshape(M, B // M, S), tgts.reshape(M, B // M, S)

        def oracle(params):
            # the honest MoE oracle is MICROBATCH-wise unpiped
            # application: capacity budgets and router means are per
            # router application (the GShard granularity), which for the
            # pipeline means per microbatch — identical token sets, so
            # loss AND grads must match exactly
            losses, auxs = [], []
            for m in range(M):
                logits, interm = model.apply(
                    {"params": params}, tk[m], mutable=["intermediates"])
                losses.append(lm_loss(logits, tg[m]))
                auxs.append(sum(
                    jnp.asarray(a).mean()
                    for a in jax.tree.leaves(interm["intermediates"])))
            return (sum(losses) / M) + 0.01 * (sum(auxs) / M)

        return (cfg, model, vs, pp_params, tk, tg, M, oracle,
                pipeline_lm_loss, stack_lm_params)

    @pytest.mark.parametrize("dropless", [False, True])
    def test_pp_moe_matches_microbatched_unpiped(self, dropless):
        """pp×ep MoE (VERDICT r04 next #2): stage bodies scan (dense, MoE)
        periods with the expert dim sharded over ep as a GSPMD auto axis;
        loss (incl. the load-balance aux term at LMTrainer's weight) and
        grads must match microbatch-wise unpiped application exactly —
        capacity dispatch AND dropless mode."""
        (cfg, model, vs, pp_params, tk, tg, M, oracle,
         pipeline_lm_loss, stack_lm_params) = self._moe_setup(dropless)
        # dp=1: capacity budgets + router means are per router
        # application, so the oracle must see the same token sets the
        # stages do — a manual dp axis would halve them (documented
        # divergence, exercised in test_pp_moe_dp_sharded_runs)
        mesh = make_mesh(MeshConfig(pp=2, ep=4))

        ref = oracle(vs["params"])
        out, metrics = jax.jit(lambda p: pipeline_lm_loss(
            cfg, p, tk, tg, mesh, M, with_moe_metrics=True))(pp_params)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   atol=2e-5)
        # drop-rate observability rides the schedule (VERDICT: preserved)
        assert float(metrics["moe_drop_rate"]) >= 0.0
        if dropless:
            assert float(metrics["moe_drop_rate"]) == 0.0

        g_pipe = jax.jit(jax.grad(lambda p: pipeline_lm_loss(
            cfg, p, tk, tg, mesh, M)))(pp_params)
        g_ref = stack_lm_params(jax.grad(oracle)(vs["params"]),
                                cfg.num_layers,
                                num_experts=cfg.num_experts,
                                moe_every=cfg.moe_every)
        flat_p, _ = jax.tree_util.tree_flatten_with_path(g_pipe)
        flat_r = jax.tree_util.tree_flatten_with_path(g_ref)[0]
        assert [p for p, _ in flat_p] == [p for p, _ in flat_r]
        for (path, a), (_, b) in zip(flat_p, flat_r):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=3e-4,
                err_msg=jax.tree_util.keystr(path))

    def test_pp_moe_dp_sharded_runs(self):
        """pp×dp×ep MoE: with the microbatch dim manually dp-sharded each
        dp rank routes its own token slice (per-shard capacity budgets —
        the documented at-scale semantics, NOT full-microbatch parity).
        Pins: finite loss, drop rate observable, dropless drops == 0."""
        (cfg, model, vs, pp_params, tk, tg, M, _oracle,
         pipeline_lm_loss, _) = self._moe_setup(dropless=True)
        mesh = make_mesh(MeshConfig(pp=2, dp=2, ep=2))
        out, metrics = jax.jit(lambda p: pipeline_lm_loss(
            cfg, p, tk, tg, mesh, M, with_moe_metrics=True))(pp_params)
        assert np.isfinite(float(out))
        assert float(metrics["moe_drop_rate"]) == 0.0
        g = jax.jit(jax.grad(lambda p: pipeline_lm_loss(
            cfg, p, tk, tg, mesh, M)))(pp_params)
        assert all(np.all(np.isfinite(np.asarray(x)))
                   for x in jax.tree.leaves(g))

    def test_pp_moe_trainer_end_to_end(self):
        """PipelineLMTrainer with a MoE config: init → train steps →
        loss decreases trend not required, but steps run, the drop rate
        lands in benchmark metrics, and 1F1B/misaligned layouts are
        rejected loudly."""
        from mpi_operator_tpu.train.lm_trainer import LMTrainerConfig
        from mpi_operator_tpu.train.pp_trainer import PipelineLMTrainer

        cfg = gpt2_config("test", attention="dense", dtype=jnp.float32,
                          vocab_size=128, max_len=16, num_layers=4,
                          num_experts=4, moe_every=2)
        mesh = make_mesh(MeshConfig(pp=2, dp=2, ep=2))
        tcfg = LMTrainerConfig(global_batch_size=16, seq_len=16,
                               warmup_steps=1)
        trainer = PipelineLMTrainer(cfg, mesh, tcfg, num_microbatches=4)
        state = trainer.init_state(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (16, 17), 0, 128)
        batch = trainer.microbatch(toks[:, :-1], toks[:, 1:])
        state, m = trainer.train_step(state, *batch)
        assert np.isfinite(float(m["loss"]))
        assert "moe_drop_rate" in m

        class Rep:
            def __iter__(self):
                return iter([batch] * 8)

        state, bm = trainer.benchmark(state, Rep(), num_steps=2,
                                      warmup_steps=1, log=lambda s: None)
        assert "moe_drop_rate" in bm
        # eval excludes the aux term: val_loss <= train loss at same params
        ev = trainer.evaluate(state, Rep(), num_batches=1)
        assert np.isfinite(ev["val_loss"])

        with pytest.raises(ValueError, match="gpipe"):
            PipelineLMTrainer(cfg, mesh, tcfg, num_microbatches=4,
                              schedule="1f1b")
        bad = gpt2_config("test", attention="dense", num_layers=2,
                          num_experts=4, moe_every=2)
        with pytest.raises(ValueError, match="whole dense\\+MoE periods"):
            PipelineLMTrainer(bad, mesh, tcfg, num_microbatches=4)

    def test_pp_trainer_evaluate(self):
        """The pp loss-only eval pass: val_loss at the current params
        equals the loss the next train_step reports (train computes loss
        BEFORE applying the update), and perplexity = exp(val_loss)."""
        import math as _math

        from mpi_operator_tpu.train.lm_trainer import LMTrainerConfig
        from mpi_operator_tpu.train.pp_trainer import PipelineLMTrainer

        cfg = gpt2_config("test", attention="dense", dtype=jnp.float32,
                          vocab_size=128, max_len=16, num_layers=2)
        mesh = make_mesh(MeshConfig(pp=2, dp=4))
        trainer = PipelineLMTrainer(
            cfg, mesh, LMTrainerConfig(global_batch_size=16, seq_len=16,
                                       warmup_steps=1),
            num_microbatches=4)
        state = trainer.init_state(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (16, 17), 0, 128)
        batch = trainer.microbatch(toks[:, :-1], toks[:, 1:])

        class Rep:
            def __iter__(self):
                return iter([batch] * 4)

        ev = trainer.evaluate(state, Rep(), num_batches=1)
        _, m = trainer.train_step(state, *batch)
        np.testing.assert_allclose(ev["val_loss"], float(m["loss"]),
                                   atol=1e-5)
        assert ev["perplexity"] == pytest.approx(
            _math.exp(ev["val_loss"]), rel=1e-6)

    def test_masked_pp_trainer_step(self):
        """End-to-end pipelined BERT through PipelineLMTrainer
        (masked_lm=True): jitted step over the 3-stream (tokens, targets,
        mask) pipeline, loss decreases."""
        from mpi_operator_tpu.models.transformer import bert_config
        from mpi_operator_tpu.train.lm_trainer import LMTrainerConfig
        from mpi_operator_tpu.train.pp_trainer import PipelineLMTrainer

        cfg = bert_config("test", attention="dense", dtype=jnp.float32,
                          vocab_size=128, max_len=16)
        mesh = make_mesh(MeshConfig(pp=2, dp=4))
        trainer = PipelineLMTrainer(
            cfg, mesh,
            LMTrainerConfig(global_batch_size=16, seq_len=16,
                            masked_lm=True, warmup_steps=1),
            num_microbatches=4)
        state = trainer.init_state(jax.random.PRNGKey(0))
        orig = jax.random.randint(jax.random.PRNGKey(1), (16, 16), 0, 128)
        mask = (jax.random.uniform(jax.random.PRNGKey(2), (16, 16))
                < 0.3).astype(jnp.float32)
        toks = jnp.where(mask > 0, 127, orig)
        losses = []
        for _ in range(5):
            state, m = trainer.train_step(
                state, *trainer.microbatch(toks, orig, mask))
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]

    def test_pp_sp_trainer_step(self):
        """End-to-end pp×sp through PipelineLMTrainer: the jitted step
        (grads + optimizer over the sp-sharded stream) runs and the loss
        decreases."""
        from mpi_operator_tpu.train.lm_trainer import LMTrainerConfig
        from mpi_operator_tpu.train.pp_trainer import PipelineLMTrainer

        cfg = gpt2_config("test", attention="ring", dtype=jnp.float32,
                          vocab_size=128, max_len=16, num_layers=2)
        mesh = make_mesh(MeshConfig(pp=2, sp=2, dp=2))
        trainer = PipelineLMTrainer(
            cfg, mesh, LMTrainerConfig(global_batch_size=16, seq_len=16),
            num_microbatches=4)
        state = trainer.init_state(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (16, 17), 0, 128)
        tk, tg = toks[:, :-1], toks[:, 1:]
        losses = []
        for _ in range(4):
            state, m = trainer.train_step(state, *trainer.microbatch(tk, tg))
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]

    def test_bubble_fraction(self):
        from mpi_operator_tpu.parallel import bubble_fraction
        assert bubble_fraction(1, 8) == 0.0
        assert bubble_fraction(4, 12) == pytest.approx(3 / 15)
        # callers pick M >= 4P: bubble stays under 20%
        assert bubble_fraction(8, 32) < 0.2


# ---------------------------------------------------------------------------
# mesh plumbing for the new axes
# ---------------------------------------------------------------------------

def test_mesh_has_all_strategy_axes():
    mesh = make_mesh(MeshConfig(dp=2, tp=2, sp=2))
    assert set(mesh.axis_names) == {"dcn", "pp", "dp", "fsdp", "ep", "sp",
                                    "tp"}


def test_mesh_rejects_wrong_device_count():
    with pytest.raises(ValueError):
        make_mesh(MeshConfig(dp=3, tp=5))


class TestPipelineTrainer:
    """End-to-end pp training: one PipelineLMTrainer step must equal one
    LMTrainer step on the same init, batch, and optimizer."""

    def _assert_matches_unpiped(self, mesh_cfg):
        """One PipelineLMTrainer step on `mesh_cfg` vs one LMTrainer step
        on a dp-only mesh: same loss, same params after sgd. Returns the
        pipeline state for sharding asserts."""
        import optax

        from mpi_operator_tpu.parallel import stack_lm_params
        from mpi_operator_tpu.train import (LMTrainer, LMTrainerConfig,
                                            PipelineLMTrainer)

        cfg = gpt2_config("test", attention="dense", dtype=jnp.float32,
                          vocab_size=256, max_len=32)
        tcfg = LMTrainerConfig(global_batch_size=16, seq_len=16)
        key = jax.random.PRNGKey(0)
        toks = jax.random.randint(jax.random.PRNGKey(5), (16, 17), 0,
                                  cfg.vocab_size)
        toks, tgts = toks[:, :-1], toks[:, 1:]

        ppt = PipelineLMTrainer(cfg, make_mesh(mesh_cfg), tcfg,
                                num_microbatches=4, tx=optax.sgd(0.1))
        s_pp = ppt.init_state(key)
        init_state = s_pp
        s_pp, m_pp = ppt.train_step(s_pp, *ppt.microbatch(toks, tgts))

        lmt = LMTrainer(CausalLM(cfg), make_mesh(MeshConfig(dp=8)), tcfg,
                        tx=optax.sgd(0.1))
        s_lm = lmt.init_state(key)
        s_lm, m_lm = lmt.train_step(s_lm, toks, tgts)

        np.testing.assert_allclose(float(m_pp["loss"]),
                                   float(m_lm["loss"]), atol=1e-5)
        ref = stack_lm_params(s_lm.params, cfg.num_layers)
        for (path, a), b in zip(
                jax.tree_util.tree_flatten_with_path(s_pp.params)[0],
                jax.tree.leaves(ref)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-5,
                err_msg=jax.tree_util.keystr(path))
        return init_state

    def test_one_step_matches_unpiped_trainer(self):
        self._assert_matches_unpiped(MeshConfig(pp=2, dp=4))

    def test_pp_tp_composes_with_megatron_shardings(self):
        """pp×tp×dp: block params placed with Megatron tp shardings
        (lm_stage_tp_specs) while pipeline_lm_loss runs tp as a GSPMD auto
        axis — the step must still equal the unpiped LMTrainer step, and
        every Megatron leaf must ACTUALLY be tp-sharded (a param rename
        that silently falls through lm_stage_tp_specs' path matching must
        fail here, not quietly lose tensor parallelism)."""
        s_pp = self._assert_matches_unpiped(MeshConfig(pp=2, tp=2, dp=2))
        blocks = s_pp.params["blocks"]
        for leaf in (blocks["mlp"]["fc_in"]["kernel"],
                     blocks["mlp"]["fc_out"]["kernel"],
                     blocks["attn"]["query"]["kernel"],
                     blocks["attn"]["key"]["kernel"],
                     blocks["attn"]["value"]["kernel"],
                     blocks["attn"]["out"]["kernel"]):
            assert "tp" in str(leaf.sharding.spec), leaf.sharding

    def test_bubble_and_validation(self):
        import optax

        from mpi_operator_tpu.train import (LMTrainerConfig,
                                            PipelineLMTrainer)

        cfg = gpt2_config("test", attention="dense", dtype=jnp.float32,
                          vocab_size=64, max_len=16)
        mesh = make_mesh(MeshConfig(pp=2, dp=4))
        t = PipelineLMTrainer(cfg, mesh,
                              LMTrainerConfig(global_batch_size=32,
                                              seq_len=8),
                              num_microbatches=8, tx=optax.sgd(0.1))
        assert t.bubble == pytest.approx(1 / 9)
        with pytest.raises(ValueError):    # M must divide over pp
            PipelineLMTrainer(cfg, mesh,
                              LMTrainerConfig(global_batch_size=24,
                                              seq_len=8),
                              num_microbatches=3, tx=optax.sgd(0.1))
        with pytest.raises(ValueError):    # microbatch must divide over dp
            PipelineLMTrainer(cfg, mesh,
                              LMTrainerConfig(global_batch_size=16,
                                              seq_len=8),
                              num_microbatches=8, tx=optax.sgd(0.1))


class TestPipeline1F1B:
    """Interleaved 1F1B (parallel/pipeline_1f1b.py): same loss/grads as
    GPipe/unpiped, strictly smaller bubble with interleaving, O(P·v)
    in-flight memory by construction (VERDICT r02 next #4)."""

    def test_schedule_invariants(self):
        from mpi_operator_tpu.parallel.pipeline_1f1b import simulate_1f1b

        for (P, M, v) in [(2, 4, 1), (4, 8, 1), (4, 8, 2), (2, 8, 2),
                          (4, 16, 4)]:
            s = simulate_1f1b(P, M, v)
            VP = v * P
            done_f = np.full((VP, M), -1)
            done_b = np.full((VP, M), -1)
            for t in range(s.ticks):
                for d in range(P):
                    if s.dir[t, d] == 0:
                        continue
                    k = s.chunk[t, d] * P + d
                    m = s.mb[t, d]
                    if s.dir[t, d] == 1:
                        # fwd dependency: previous virtual stage finished
                        assert done_f[k, m] == -1
                        if k > 0:
                            assert 0 <= done_f[k - 1, m] < t
                        done_f[k, m] = t
                    else:
                        assert done_b[k, m] == -1
                        if k == VP - 1:
                            assert 0 <= done_f[k, m] < t
                        else:
                            assert 0 <= done_b[k + 1, m] < t
                        done_b[k, m] = t
            assert (done_f >= 0).all() and (done_b >= 0).all()

    def test_interleaving_shrinks_the_bubble(self):
        """The VERDICT criterion: measurably fewer idle ticks at pp=4.
        v=2 at pp=4/M=8 nearly halves the idle fraction; v=1 in-flight
        memory is O(P), not O(M)."""
        from mpi_operator_tpu.parallel.pipeline_1f1b import simulate_1f1b

        s1 = simulate_1f1b(4, 8, 1)
        s2 = simulate_1f1b(4, 8, 2)
        assert s2.bubble_fraction < 0.65 * s1.bubble_fraction
        assert s1.h_depth <= 4            # O(P): GPipe holds all M=8
        s_big = simulate_1f1b(4, 32, 1)
        assert s_big.h_depth <= 4         # independent of M

    def _parity(self, pp, dp, v, L):
        from flax.core import meta
        from mpi_operator_tpu.parallel.pipeline import (
            pipeline_lm_loss, stack_lm_params)
        from mpi_operator_tpu.parallel.pipeline_1f1b import (
            interleave_blocks, pipeline_lm_1f1b_grads)

        cfg = gpt2_config("test", attention="dense", dtype=jnp.float32,
                          vocab_size=128, max_len=16, num_layers=L)
        mesh = make_mesh(MeshConfig(pp=pp, dp=dp))
        model = CausalLM(cfg)
        M, mb, S = 2 * pp, 2, 16
        toks = jax.random.randint(jax.random.PRNGKey(1), (M, mb, S), 0, 128)
        tgts = jnp.roll(toks, -1, axis=-1)
        vs = meta.unbox(model.init(jax.random.PRNGKey(0),
                                   jnp.zeros((2, S), jnp.int32)))
        pp_params = stack_lm_params(vs["params"], cfg.num_layers)
        loss_g, grads_g = jax.jit(jax.value_and_grad(
            lambda p: pipeline_lm_loss(cfg, p, toks, tgts, mesh, M)))(
                pp_params)
        params_v = dict(pp_params)
        params_v["blocks"] = interleave_blocks(pp_params["blocks"], pp, v)
        loss_f, grads_f = jax.jit(lambda p: pipeline_lm_1f1b_grads(
            cfg, p, toks, tgts, mesh, M, interleave=v))(params_v)
        np.testing.assert_allclose(np.asarray(loss_g), np.asarray(loss_f),
                                   atol=1e-5)
        gb = interleave_blocks(grads_g["blocks"], pp, v)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5),
            gb, grads_f["blocks"])
        for k in ("wte", "wpe"):
            np.testing.assert_allclose(np.asarray(grads_g[k]),
                                       np.asarray(grads_f[k]), atol=1e-5)

    def test_1f1b_matches_gpipe_pp2(self):
        self._parity(pp=2, dp=4, v=1, L=2)

    def test_1f1b_interleaved_matches_gpipe(self):
        self._parity(pp=2, dp=4, v=2, L=4)

    @pytest.mark.parametrize("v", [1, 2])
    def test_1f1b_masked_matches_gpipe(self, v):
        """Masked-LM (BERT) under 1F1B (VERDICT r04 next #3): the mask is
        consumed at the last virtual stage, the divisor is the DYNAMIC
        global mask count — loss and grads must match the GPipe
        pipeline_mlm_loss + jax.grad on identical params."""
        from flax.core import meta
        from mpi_operator_tpu.models.transformer import (MaskedLM,
                                                         bert_config)
        from mpi_operator_tpu.parallel.pipeline import (pipeline_mlm_loss,
                                                        stack_mlm_params)
        from mpi_operator_tpu.parallel.pipeline_1f1b import (
            interleave_blocks, pipeline_lm_1f1b_grads)

        cfg = bert_config("test", attention="dense", dtype=jnp.float32,
                          vocab_size=128, max_len=16, num_layers=2 * v)
        mesh = make_mesh(MeshConfig(pp=2, dp=4))
        model = MaskedLM(cfg)
        M, mb, S = 4, 2, 16
        orig = jax.random.randint(jax.random.PRNGKey(1), (M, mb, S), 0, 128)
        msk = (jax.random.uniform(jax.random.PRNGKey(5), (M, mb, S))
               < 0.25).astype(jnp.float32)
        toks = jnp.where(msk > 0, cfg.vocab_size - 1, orig)
        vs = meta.unbox(model.init(jax.random.PRNGKey(0),
                                   jnp.zeros((2, S), jnp.int32)))
        pp_params = stack_mlm_params(vs["params"], cfg.num_layers)
        loss_g, grads_g = jax.jit(jax.value_and_grad(
            lambda p: pipeline_mlm_loss(cfg, p, toks, orig, msk, mesh, M)))(
                pp_params)
        params_v = dict(pp_params)
        params_v["blocks"] = interleave_blocks(pp_params["blocks"], 2, v)
        loss_f, grads_f = jax.jit(lambda p: pipeline_lm_1f1b_grads(
            cfg, p, toks, orig, mesh, M, interleave=v, mask=msk))(params_v)
        np.testing.assert_allclose(np.asarray(loss_g), np.asarray(loss_f),
                                   atol=2e-5)
        gb = interleave_blocks(grads_g["blocks"], 2, v)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4),
            gb, grads_f["blocks"])
        for k in ("wte", "mlm_bias", "mlm_dense", "ln_emb"):
            jax.tree.map(lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-4),
                grads_g[k], grads_f[k])

    def test_1f1b_masked_sp_ring_matches_gpipe(self):
        """The full composition: masked-LM × sp × 1F1B — bidirectional
        ring stage bodies, sp-sharded mask stream, dynamic divisor, all
        under the in-schedule vjp. Pinned against the GPipe mlm path."""
        from flax.core import meta
        from mpi_operator_tpu.models.transformer import (MaskedLM,
                                                         bert_config)
        import dataclasses
        from mpi_operator_tpu.parallel.pipeline import (pipeline_mlm_loss,
                                                        stack_mlm_params)
        from mpi_operator_tpu.parallel.pipeline_1f1b import (
            pipeline_lm_1f1b_grads)

        cfg = bert_config("test", attention="ring", dtype=jnp.float32,
                          vocab_size=128, max_len=32)
        mesh = make_mesh(MeshConfig(pp=2, sp=2, dp=2))
        model = MaskedLM(dataclasses.replace(cfg, attention="dense"))
        M, mb, S = 4, 2, 32
        orig = jax.random.randint(jax.random.PRNGKey(1), (M, mb, S), 0, 128)
        msk = (jax.random.uniform(jax.random.PRNGKey(5), (M, mb, S))
               < 0.25).astype(jnp.float32)
        toks = jnp.where(msk > 0, cfg.vocab_size - 1, orig)
        vs = meta.unbox(model.init(jax.random.PRNGKey(0),
                                   jnp.zeros((2, S), jnp.int32)))
        pp_params = stack_mlm_params(vs["params"], cfg.num_layers)
        loss_g, grads_g = jax.jit(jax.value_and_grad(
            lambda p: pipeline_mlm_loss(cfg, p, toks, orig, msk, mesh, M)))(
                pp_params)
        loss_f, grads_f = jax.jit(lambda p: pipeline_lm_1f1b_grads(
            cfg, p, toks, orig, mesh, M, mask=msk))(pp_params)
        np.testing.assert_allclose(np.asarray(loss_g), np.asarray(loss_f),
                                   rtol=1e-4)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4),
            grads_g["blocks"], grads_f["blocks"])

    def test_1f1b_sp_ring_matches_gpipe(self):
        """pp×sp under 1F1B (VERDICT r04 next #3): the streams' sequence
        dim sharded over sp, stage attention ringing in-schedule — loss
        and grads must match the GPipe pp×sp path on identical params."""
        from flax.core import meta
        from mpi_operator_tpu.parallel.pipeline import (pipeline_lm_loss,
                                                        stack_lm_params)
        from mpi_operator_tpu.parallel.pipeline_1f1b import (
            pipeline_lm_1f1b_grads)

        cfg = gpt2_config("test", attention="ring", dtype=jnp.float32,
                          vocab_size=128, max_len=32)
        mesh = make_mesh(MeshConfig(pp=2, sp=2, dp=2))
        import dataclasses
        model = CausalLM(dataclasses.replace(cfg, attention="dense"))
        M, mb, S = 4, 2, 32
        toks = jax.random.randint(jax.random.PRNGKey(1), (M, mb, S), 0, 128)
        tgts = jnp.roll(toks, -1, axis=-1)
        vs = meta.unbox(model.init(jax.random.PRNGKey(0),
                                   jnp.zeros((2, S), jnp.int32)))
        pp_params = stack_lm_params(vs["params"], cfg.num_layers)
        loss_g, grads_g = jax.jit(jax.value_and_grad(
            lambda p: pipeline_lm_loss(cfg, p, toks, tgts, mesh, M)))(
                pp_params)
        loss_f, grads_f = jax.jit(lambda p: pipeline_lm_1f1b_grads(
            cfg, p, toks, tgts, mesh, M))(pp_params)
        # rtol, not tight atol: the 1F1B per-stage recompute-vjp orders
        # the ring reductions differently from GPipe's autodiff — f32
        # noise at ~2.5e-5 relative on this config
        np.testing.assert_allclose(np.asarray(loss_g), np.asarray(loss_f),
                                   rtol=1e-4)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4),
            grads_g["blocks"], grads_f["blocks"])

    def test_1f1b_trainer_step(self):
        """End-to-end: PipelineLMTrainer(schedule='1f1b', interleave=2)
        runs a full train step (grads in-schedule + optimizer) and the
        loss decreases over a few steps."""
        from mpi_operator_tpu.train.lm_trainer import LMTrainerConfig
        from mpi_operator_tpu.train.pp_trainer import PipelineLMTrainer

        cfg = gpt2_config("test", attention="dense", dtype=jnp.float32,
                          vocab_size=128, max_len=16, num_layers=4)
        mesh = make_mesh(MeshConfig(pp=2, dp=4))
        M, S = 4, 16
        tcfg = LMTrainerConfig(global_batch_size=4 * M, seq_len=S,
                               warmup_steps=1)
        tr = PipelineLMTrainer(cfg, mesh, tcfg, num_microbatches=M,
                               schedule="1f1b", interleave=2)
        state = tr.init_state(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(2),
                                  (tcfg.global_batch_size, S + 1), 0, 128)
        stream = tr.microbatch(toks[:, :-1], toks[:, 1:])
        losses = []
        for _ in range(5):
            state, m = tr.train_step(state, *stream)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses
        assert int(state.step) == 5

    def test_checkpoint_layout_is_schedule_agnostic(self):
        """Checkpoints are written in canonical layer order regardless of
        schedule, so a gpipe checkpoint resumes under 1f1b×2 (and back)
        without silently permuting layers."""
        from mpi_operator_tpu.train.lm_trainer import LMTrainerConfig
        from mpi_operator_tpu.train.pp_trainer import PipelineLMTrainer

        cfg = gpt2_config("test", attention="dense", dtype=jnp.float32,
                          vocab_size=128, max_len=16, num_layers=4)
        mesh = make_mesh(MeshConfig(pp=2, dp=4))
        tcfg = LMTrainerConfig(global_batch_size=16, seq_len=16,
                               warmup_steps=1)
        g = PipelineLMTrainer(cfg, mesh, tcfg, num_microbatches=4)
        f = PipelineLMTrainer(cfg, mesh, tcfg, num_microbatches=4,
                              schedule="1f1b", interleave=2)
        gs = g.init_state(jax.random.PRNGKey(0))
        fs = f.init_state(jax.random.PRNGKey(0))
        # same seed → identical canonical params (the live layouts differ)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
            g.canonical_state(gs).params, f.canonical_state(fs).params)
        # evaluate() must de-interleave before the GPipe eval pass — with
        # the raw chunk layout the stages would apply layers out of order
        toks = jax.random.randint(jax.random.PRNGKey(3), (16, 17), 0, 128)
        batch = g.microbatch(toks[:, :-1], toks[:, 1:])

        class Rep:
            def __iter__(self):
                return iter([batch] * 2)

        ev_g = g.evaluate(gs, Rep(), num_batches=1)
        ev_f = f.evaluate(fs, Rep(), num_batches=1)
        np.testing.assert_allclose(ev_g["val_loss"], ev_f["val_loss"],
                                   rtol=1e-5)
        # live layouts really are permuted relative to each other
        diff = jax.tree.leaves(jax.tree.map(
            lambda a, b: float(jnp.abs(a - b).max()),
            gs.params["blocks"], fs.params["blocks"]))
        assert max(diff) > 0
        # roundtrip is exact
        back = f.from_canonical_state(f.canonical_state(fs))
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), fs.params, back.params)


class TestMoeDropless:
    """VERDICT r02 weak #8: capacity dispatch drops load-imbalanced
    tokens silently. The drop RATE is now observable (sown intermediate)
    and a dropless mode exists."""

    def _m(self, **kw):
        from mpi_operator_tpu.parallel import MoeMlp
        base = dict(num_experts=4, embed_dim=32, mlp_dim=64, top_k=2,
                    capacity_factor=1.25, dtype=jnp.float32)
        base.update(kw)
        m = MoeMlp(**base)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32))
        vs = meta.unbox(m.init(jax.random.PRNGKey(1), x))
        return m, x, vs

    def _drop_rate(self, m, vs, x):
        (_, _), diag = m.apply(vs, x, mutable=["diagnostics"])
        return float(jax.tree.leaves(diag["diagnostics"])[0])

    def test_drop_rate_sane_at_default_capacity(self):
        """With a freshly-initialized (≈uniform) router and capacity
        factor 1.25, the drop rate must stay small — silent heavy
        dropping at the default config was the original complaint."""
        m, x, vs = self._m()
        rate = self._drop_rate(m, vs, x)
        assert 0.0 <= rate <= 0.25, rate

    def test_drop_rate_reports_starvation(self):
        m, x, vs = self._m(capacity_factor=0.01)
        rate = self._drop_rate(m, vs, x)
        assert rate >= 0.8, rate

    def test_dropless_matches_infinite_capacity(self):
        """Dropless == capacity dispatch with a budget nothing exceeds
        (same routing semantics, no dropped tokens)."""
        m_cap, x, vs = self._m(capacity_factor=100.0)
        ref, aux_ref = m_cap.apply(vs, x)
        m_free = self._m(dropless=True)[0]
        out, aux = m_free.apply(vs, x)        # identical param structure
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   atol=2e-5)
        np.testing.assert_allclose(float(aux_ref), float(aux), atol=1e-6)
        assert self._drop_rate(m_free, vs, x) == 0.0

    def test_dropless_ep_sharded_matches_dense(self):
        """The dropless path still shards experts over ep."""
        from mpi_operator_tpu.parallel.sharding import param_shardings

        m, x, vs = self._m(dropless=True)
        ref, _ = m.apply(vs, x)
        mesh = make_mesh(MeshConfig(dp=2, ep=4))
        abstract = jax.eval_shape(lambda r: m.init(r, x),
                                  jax.random.PRNGKey(1))
        sh = param_shardings(mesh, abstract)
        out_sh = jax.tree.unflatten(
            jax.tree.structure(meta.unbox(abstract)), jax.tree.leaves(sh))
        vs_sharded = jax.jit(lambda v: v, out_shardings=out_sh)(vs)
        xs = jax.device_put(x, NamedSharding(mesh, P(("dp",))))
        out2, _ = jax.jit(m.apply)(vs_sharded, xs)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out2),
                                   atol=1e-5)


# ---------------------------------------------------------------------------
# ring collective-matmul (tp_overlap)
# ---------------------------------------------------------------------------

@pytest.mark.multichip
class TestRingCollectiveMatmul:
    """allgather_matmul / matmul_reducescatter against the einsum oracle.

    The ring decomposition (ppermute hops hidden behind per-shard matmuls)
    must be a pure re-schedule: same values forward AND backward, where the
    backward runs the mirrored ring via custom_vjp. Cotangents come from a
    nonlinear loss so each output element gets a distinct pullback.
    ring="bidir" halves each shard and rotates the halves in opposite
    directions (half the bytes per hop); with Sl=2 on the 4-ring below the
    halves are 1+1, so the odd-split arithmetic is exercised too."""

    def _mesh(self):
        return make_mesh(MeshConfig(dp=2, tp=4))

    @pytest.mark.parametrize("tp_ring", ["uni", "bidir"])
    def test_allgather_matmul_matches_einsum(self, tp_ring):
        from mpi_operator_tpu.parallel.collectives import allgather_matmul
        from mpi_operator_tpu.utils.compat import shard_map

        mesh = self._mesh()
        k0, k1 = jax.random.split(jax.random.PRNGKey(0))
        x = jax.random.normal(k0, (2, 8, 16), jnp.float32)    # rows over tp
        w = jax.random.normal(k1, (16, 12), jnp.float32)      # cols over tp

        ring = shard_map(
            lambda xl, wl: allgather_matmul(xl, wl, "tp", ring=tp_ring),
            mesh=mesh,
            in_specs=(P("dp", "tp", None), P(None, "tp")),
            out_specs=P("dp", None, "tp"), check_vma=False)

        def loss_ring(x, w):
            return jnp.sin(ring(x, w)).sum()

        def loss_ref(x, w):
            return jnp.sin(jnp.einsum("bsk,kn->bsn", x, w)).sum()

        np.testing.assert_allclose(
            np.asarray(ring(x, w)), np.asarray(x @ w), atol=1e-5)
        g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1)))(x, w)
        g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1)))(x, w)
        for a, b in zip(g_ring, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("tp_ring", ["uni", "bidir"])
    def test_matmul_reducescatter_matches_einsum(self, tp_ring):
        from mpi_operator_tpu.parallel.collectives import matmul_reducescatter
        from mpi_operator_tpu.utils.compat import shard_map

        mesh = self._mesh()
        k0, k1 = jax.random.split(jax.random.PRNGKey(1))
        x = jax.random.normal(k0, (2, 8, 16), jnp.float32)    # K over tp
        w = jax.random.normal(k1, (16, 12), jnp.float32)      # rows over tp

        ring = shard_map(
            lambda xl, wl: matmul_reducescatter(xl, wl, "tp", ring=tp_ring),
            mesh=mesh,
            in_specs=(P("dp", None, "tp"), P("tp", None)),
            out_specs=P("dp", "tp", None), check_vma=False)

        def loss_ring(x, w):
            return jnp.sin(ring(x, w)).sum()

        def loss_ref(x, w):
            return jnp.sin(jnp.einsum("bsk,kn->bsn", x, w)).sum()

        np.testing.assert_allclose(
            np.asarray(ring(x, w)), np.asarray(x @ w), atol=1e-5)
        g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1)))(x, w)
        g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1)))(x, w)
        for a, b in zip(g_ring, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("tp_ring", ["uni", "bidir"])
    def test_non_divisible_rows_padded(self, tp_ring):
        """S=6 over a 4-ring: the internal zero-row pad takes it to 8,
        pad rows land at the END of the global output (highest ranks) as
        exact zeros, and grads flow correctly through the caller's
        slice."""
        from mpi_operator_tpu.parallel.collectives import matmul_reducescatter
        from mpi_operator_tpu.utils.compat import shard_map

        mesh = self._mesh()
        k0, k1 = jax.random.split(jax.random.PRNGKey(3))
        x = jax.random.normal(k0, (6, 16), jnp.float32)
        w = jax.random.normal(k1, (16, 12), jnp.float32)
        f = shard_map(
            lambda xl, wl: matmul_reducescatter(xl, wl, "tp", ring=tp_ring),
            mesh=mesh,
            in_specs=(P(None, "tp"), P("tp", None)),
            out_specs=P("tp", None), check_vma=False)
        out = f(x, w)
        assert out.shape == (8, 12)              # 4 * ceil(6/4)
        np.testing.assert_allclose(np.asarray(out[:6]), np.asarray(x @ w),
                                   atol=1e-5)
        assert np.all(np.asarray(out[6:]) == 0.0)

        def loss_ring(x, w):
            return jnp.sin(f(x, w)[:6]).sum()    # caller slices the pad

        def loss_ref(x, w):
            return jnp.sin(x @ w).sum()

        g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1)))(x, w)
        g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1)))(x, w)
        for a, b in zip(g_ring, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-5)

    def test_contraction_mismatch_rejected(self):
        from mpi_operator_tpu.parallel.collectives import allgather_matmul

        with pytest.raises(ValueError, match="contraction mismatch"):
            allgather_matmul(jnp.ones((4, 8)), jnp.ones((16, 4)))

    def test_tp_overlap_train_step_matches_oracle(self):
        """tp_overlap=True is a latency optimization, never a numerics
        change: the full train step (qkv/out/ffn rings + the overlapped
        fused LM loss) must track the einsum path loss-for-loss across an
        optimizer update."""
        import optax

        from mpi_operator_tpu.train import LMTrainer, LMTrainerConfig

        toks = jax.random.randint(jax.random.PRNGKey(5), (8, 17), 0, 256)
        toks, tgts = toks[:, :-1], toks[:, 1:]
        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
        outs = {}
        for mode in ("einsum", "uni", "bidir"):
            cfg = gpt2_config("test", attention="dense", dtype=jnp.float32,
                              vocab_size=256, max_len=32,
                              tp_overlap=mode != "einsum",
                              tp_ring="bidir" if mode == "bidir" else "uni")
            t = LMTrainer(CausalLM(cfg), mesh,
                          LMTrainerConfig(global_batch_size=8, seq_len=16,
                                          fused_xent=True),
                          tx=optax.sgd(0.1))
            s = t.init_state(jax.random.PRNGKey(0))
            s, m1 = t.train_step(s, toks, tgts)
            s, m2 = t.train_step(s, toks, tgts)   # after a real update
            outs[mode] = (float(m1["loss"]), float(m2["loss"]))
        np.testing.assert_allclose(outs["uni"], outs["einsum"], rtol=2e-6)
        np.testing.assert_allclose(outs["bidir"], outs["einsum"], rtol=2e-6)

    def test_tp_overlap_non_divisible_seq_and_vocab(self):
        """seq=15 and vocab=255 over tp=2: the overlap bodies zero-pad
        internally (seq rows masked out, pad vocab columns forced to
        -inf before the softmax normalizer) instead of raising — the
        loss must equal the einsum path's exactly."""
        import optax

        from mpi_operator_tpu.train import LMTrainer, LMTrainerConfig

        toks = jax.random.randint(jax.random.PRNGKey(9), (8, 16), 0, 255)
        toks, tgts = toks[:, :-1], toks[:, 1:]
        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
        outs = {}
        for mode in ("einsum", "uni", "bidir"):
            cfg = gpt2_config("test", attention="dense", dtype=jnp.float32,
                              vocab_size=255, max_len=32,
                              tp_overlap=mode != "einsum",
                              tp_ring="bidir" if mode == "bidir" else "uni")
            t = LMTrainer(CausalLM(cfg), mesh,
                          LMTrainerConfig(global_batch_size=8, seq_len=15,
                                          fused_xent=True),
                          tx=optax.sgd(0.1))
            s = t.init_state(jax.random.PRNGKey(0))
            s, m1 = t.train_step(s, toks, tgts)
            s, m2 = t.train_step(s, toks, tgts)
            outs[mode] = (float(m1["loss"]), float(m2["loss"]))
        np.testing.assert_allclose(outs["uni"], outs["einsum"], rtol=2e-6)
        np.testing.assert_allclose(outs["bidir"], outs["einsum"], rtol=2e-6)
