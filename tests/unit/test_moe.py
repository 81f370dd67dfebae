"""MoE gating + expert-parallel layer tests (reference: tests/unit/test_moe.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.moe import MoE, MOELayer, TopKGate, top1gating, top2gating
from deepspeed_tpu.moe.experts import ExpertMLP

D = 8
E = 4


class TestTop1Gating:
    def test_shapes(self):
        logits = jax.random.normal(jax.random.PRNGKey(0), (16, E))
        l_aux, combine, dispatch, counts, stats = top1gating(
            logits, capacity_factor=2.0, min_capacity=1)
        cap = max(1, int(np.ceil(16 / E * 2.0)))
        assert combine.shape == (16, E, cap)
        assert dispatch.shape == (16, E, cap)
        assert counts.shape == (E,)
        assert np.isfinite(float(l_aux))

    def test_all_tokens_dispatched_when_capacity_ample(self):
        logits = jax.random.normal(jax.random.PRNGKey(1), (16, E))
        _, combine, dispatch, _, _ = top1gating(
            logits, capacity_factor=float(E), min_capacity=16)
        # each token occupies exactly one (expert, slot)
        per_token = dispatch.sum(axis=(1, 2))
        np.testing.assert_array_equal(np.asarray(per_token), np.ones(16))

    def test_capacity_drops_tokens(self):
        # all tokens prefer expert 0; capacity 2 keeps only 2
        logits = jnp.stack([jnp.full((16,), 5.0)] + [jnp.zeros(16)] * (E - 1),
                           axis=1)
        _, _, dispatch, _, _ = top1gating(logits, capacity_factor=0.5,
                                          min_capacity=2)
        kept = float(dispatch.sum())
        assert kept == 2.0

    def test_l_aux_uniform_is_one(self):
        # perfectly uniform router → l_aux == 1 (E * E * (1/E²))
        logits = jnp.zeros((E * 8, E))
        l_aux, _, _, _, _ = top1gating(logits, capacity_factor=2.0,
                                       min_capacity=64)
        # argmax breaks ties to expert 0 → ce is one-hot; me uniform
        # so l_aux = E * sum(me*ce) = E * 1/E = 1
        assert float(l_aux) == pytest.approx(1.0, rel=1e-5)

    def test_combine_weights_are_gate_probs(self):
        logits = jax.random.normal(jax.random.PRNGKey(2), (8, E))
        gates = jax.nn.softmax(logits, axis=-1)
        _, combine, dispatch, _, _ = top1gating(
            logits, capacity_factor=float(E), min_capacity=8)
        sel = np.asarray(jnp.argmax(logits, axis=-1))
        w = np.asarray(combine.sum(axis=2))  # [S, E]
        for s in range(8):
            assert w[s, sel[s]] == pytest.approx(
                float(gates[s, sel[s]]), rel=1e-5)


class TestTop2Gating:
    def test_shapes_and_two_experts(self):
        logits = jax.random.normal(jax.random.PRNGKey(3), (16, E))
        l_aux, combine, dispatch, counts, stats = top2gating(
            logits, capacity_factor=float(E), min_capacity=32)
        per_token_experts = (dispatch.sum(axis=2) > 0).sum(axis=1)
        np.testing.assert_array_equal(np.asarray(per_token_experts),
                                      np.full(16, 2))
        # combine weights normalized over the two experts
        np.testing.assert_allclose(np.asarray(combine.sum(axis=(1, 2))),
                                   np.ones(16), rtol=1e-5)

    def test_second_differs_from_first(self):
        logits = jax.random.normal(jax.random.PRNGKey(4), (16, E))
        _, _, dispatch, _, _ = top2gating(logits, capacity_factor=float(E),
                                          min_capacity=32)
        experts_hit = np.asarray(dispatch.sum(axis=2))  # [S, E] 0/1
        assert (experts_hit.max(axis=1) <= 1).all()


class TestMOELayer:
    def test_parity_with_per_token_expert(self):
        """k=1, ample capacity: y[token] == gate_prob * expert(token)."""
        gate = TopKGate(D, E, k=1, capacity_factor=float(E), min_capacity=64)
        expert = ExpertMLP(D, 2 * D)
        layer = MOELayer(gate, expert, E)
        rng = jax.random.PRNGKey(5)
        x = jax.random.normal(jax.random.PRNGKey(6), (16, D))
        params = layer.init_params(rng, x)
        y, l_aux, counts = layer.apply(params, x, train=False)
        assert y.shape == x.shape
        assert float(counts.sum()) == 16

        logits = np.asarray(x.astype(jnp.float32) @ params["gate"]["wg"])
        gates = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
        sel = logits.argmax(axis=-1)
        for s in range(16):
            p_e = jax.tree.map(lambda a: a[sel[s]], params["experts"])
            expected = gates[s, sel[s]] * np.asarray(
                expert.apply(p_e, x[s:s + 1]))[0]
            np.testing.assert_allclose(np.asarray(y[s]), expected, rtol=1e-4)

    def test_batched_input_shape(self):
        gate = TopKGate(D, E, k=2, capacity_factor=2.0)
        layer = MOELayer(gate, ExpertMLP(D), E)
        x = jax.random.normal(jax.random.PRNGKey(7), (2, 8, D))
        params = layer.init_params(jax.random.PRNGKey(8), x)
        y, l_aux, _ = layer.apply(params, x, train=False)
        assert y.shape == x.shape


class TestMoEWrapper:
    def test_requires_divisible_experts(self):
        deepspeed_tpu.initialize_mesh(expert=4, data=-1)
        with pytest.raises(ValueError, match="divide"):
            MoE(hidden_size=D, num_experts=6)

    def test_expert_params_sharded(self):
        deepspeed_tpu.initialize_mesh(expert=4, data=-1)
        moe = MoE(hidden_size=D, num_experts=4, k=1)
        assert moe.num_local_experts == 1
        x = jnp.zeros((8, D))
        params = moe.init_params(jax.random.PRNGKey(0), x)
        specs = moe.param_partition_specs(params)
        from jax.sharding import PartitionSpec
        for leaf in jax.tree.leaves(
                specs["experts"],
                is_leaf=lambda s: isinstance(s, PartitionSpec)):
            assert leaf == PartitionSpec("expert")

    def test_training_decreases_loss(self):
        """MoE regression model trained through the engine on an expert=4
        mesh (the reference's SimpleMoEModel scenario, simple_model.py:234)."""
        deepspeed_tpu.initialize_mesh(expert=4, data=-1)
        moe = MoE(hidden_size=D, num_experts=4, k=1, capacity_factor=4.0,
                  min_capacity=64)
        rng = jax.random.PRNGKey(0)
        x0 = jnp.zeros((16, D))
        moe_params = moe.init_params(rng, x0)
        k1, k2 = jax.random.split(jax.random.PRNGKey(1))
        head = jax.random.normal(k1, (D, D)) * 0.3
        params = {"moe": moe_params, "head": head}

        def model(p, rng, x, y):
            h, l_aux, _ = moe.apply(p["moe"], x, rng=rng)
            pred = h @ p["head"]
            return jnp.mean((pred - y) ** 2) + 0.01 * l_aux

        config = {
            "train_batch_size": 16,
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
            "zero_optimization": {"stage": 2},
            "steps_per_print": 100,
        }
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, config=config, model_parameters=params)
        rs = np.random.RandomState(0)
        w = rs.randn(D, D).astype(np.float32)
        xb = rs.randn(16, D).astype(np.float32)
        yb = xb @ w
        losses = []
        for i in range(50):
            loss = engine.forward(xb, yb)
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.3, losses

    def test_moe_zero_specs_no_duplicate_axis(self):
        """ZeRO partitioning must not reuse the expert axis already claimed
        by stacked expert params."""
        deepspeed_tpu.initialize_mesh(expert=4, data=-1)
        from deepspeed_tpu.parallel.mesh import get_mesh_context
        from deepspeed_tpu.runtime.zero.partition import ZeroPartitioner
        moe = MoE(hidden_size=D, num_experts=4, k=1)
        params = moe.init_params(jax.random.PRNGKey(0), jnp.zeros((8, D)))
        specs = moe.param_partition_specs(params)
        zp = ZeroPartitioner(get_mesh_context(), stage=2)
        shardings = zp.grad_shardings(params, specs)
        for s in jax.tree.leaves(shardings):
            axes = []
            for part in s.spec:
                if part is None:
                    continue
                axes.extend(part if isinstance(part, tuple) else (part,))
            assert len(axes) == len(set(axes)), s.spec


class TestScatterDispatch:
    """The scatter dispatcher must route identically to the GShard einsum
    reference (same gating, O(S*k*d) memory instead of O(S*E*C))."""

    @pytest.mark.parametrize("k", [1, 2])
    def test_scatter_matches_einsum(self, k):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from deepspeed_tpu.moe.sharded_moe import MOELayer, TopKGate
        from deepspeed_tpu.moe.experts import ExpertMLP

        d, e = 16, 4
        gate = TopKGate(d, e, k=k, capacity_factor=1.0)
        expert = ExpertMLP(d, 32)
        scatter = MOELayer(gate, expert, e, dispatch_impl="scatter")
        einsum = MOELayer(gate, expert, e, dispatch_impl="einsum")
        x = jax.random.normal(jax.random.PRNGKey(0), (64, d), jnp.float32)
        params = scatter.init_params(jax.random.PRNGKey(1), x)
        y_s, aux_s, cnt_s = scatter.apply(params, x)
        y_e, aux_e, cnt_e = einsum.apply(params, x)
        np.testing.assert_allclose(np.asarray(y_s), np.asarray(y_e),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(float(aux_s), float(aux_e), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(cnt_s), np.asarray(cnt_e))

    def test_scatter_gradients_match_einsum(self):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from deepspeed_tpu.moe.sharded_moe import MOELayer, TopKGate
        from deepspeed_tpu.moe.experts import ExpertMLP

        d, e = 16, 4
        gate = TopKGate(d, e, k=2, capacity_factor=1.25)
        expert = ExpertMLP(d, 32)
        scatter = MOELayer(gate, expert, e, dispatch_impl="scatter")
        einsum = MOELayer(gate, expert, e, dispatch_impl="einsum")
        x = jax.random.normal(jax.random.PRNGKey(2), (64, d), jnp.float32)
        params = scatter.init_params(jax.random.PRNGKey(3), x)

        def loss(layer):
            def f(p):
                y, aux, _ = layer.apply(p, x)
                return (y.astype(jnp.float32) ** 2).mean() + 0.01 * aux
            return f

        g_s = jax.grad(loss(scatter))(params)
        g_e = jax.grad(loss(einsum))(params)
        for a, b in zip(jax.tree.leaves(g_s), jax.tree.leaves(g_e)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-6)


class TestManualTP:
    """MoE expert FFNs under MANUAL tensor parallelism (round 5): the
    group pipe body's apply_manual(tp_axis=) must match the replicated
    apply_with_aux exactly — forward AND per-leaf grads — at tp in
    {2, 4}.  Reference slot: the expert FFN position of
    moe/sharded_moe.py:312 under Megatron mp."""

    def _parity(self, tp):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P

        import deepspeed_tpu
        from deepspeed_tpu.models import GPTMoEConfig
        from deepspeed_tpu.models.gpt_moe_pipe import GPTMoEGroupPipe

        deepspeed_tpu.reset_mesh_context()
        ctx = deepspeed_tpu.initialize_mesh(model=tp, data=-1)
        cfg = GPTMoEConfig(
            vocab_size=64, n_positions=32, hidden_size=32, num_layers=4,
            num_heads=4, bf16=False, num_experts=4, top_k=2,
            capacity_factor=2.0, min_capacity=4, moe_every=2,
            embd_dropout=0.0, attn_dropout=0.0, hidden_dropout=0.0)
        grp = GPTMoEGroupPipe(cfg)
        assert grp.supports_manual_tp(tp)
        params = grp.init_params(jax.random.PRNGKey(0), None)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32),
                              jnp.float32)

        def loss_ref(p):
            y, aux = grp.apply_with_aux(p, x, rng=None)
            return (y.astype(jnp.float32) ** 2).sum() * 1e-3 + aux

        # jitted: un-jitted, the region and the reference's grad dispatch
        # op by op over the eight devices
        l_ref, g_ref = jax.jit(jax.value_and_grad(loss_ref))(params)

        pv = grp.tp_manual_views(params)
        specs = grp.tp_manual_view_specs()

        def region(pl, xl):
            def f(pp):
                y, aux = grp.apply_manual(pp, xl, rng=None,
                                          tp_axis="model")
                return (y.astype(jnp.float32) ** 2).sum() * 1e-3 + aux
            return jax.value_and_grad(f)(pl)

        fn = jax.jit(jax.shard_map(
            region, mesh=ctx.mesh, in_specs=(specs, P()),
            out_specs=(P(), specs), check_vma=False))
        l_tp, g_tp_v = fn(pv, x)
        g_tp = grp.tp_manual_unview(g_tp_v)
        np.testing.assert_allclose(float(l_tp), float(l_ref), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_tp)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=2e-5)
        deepspeed_tpu.reset_mesh_context()

    def test_group_layer_parity_tp2(self):
        self._parity(2)

    def test_group_layer_parity_tp4(self):
        self._parity(4)

    def test_einsum_dispatch_tp_parity(self):
        """The einsum dispatch path's tp_axis branch (fcast on the
        dispatch input only, apply_tp experts) must match the replicated
        einsum layer — fwd and grads."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P

        import deepspeed_tpu
        from deepspeed_tpu.moe.experts import ExpertMLP
        from deepspeed_tpu.moe.sharded_moe import MOELayer, TopKGate

        deepspeed_tpu.reset_mesh_context()
        ctx = deepspeed_tpu.initialize_mesh(model=2, data=-1)
        d, e = 16, 4
        gate = TopKGate(d, e, k=2, capacity_factor=2.0)
        layer = MOELayer(gate, ExpertMLP(d, 32), e, dispatch_impl="einsum")
        x = jax.random.normal(jax.random.PRNGKey(2), (64, d), jnp.float32)
        params = layer.init_params(jax.random.PRNGKey(3), x)

        def loss_ref(p):
            y, aux, _ = layer.apply(p, x)
            return (y.astype(jnp.float32) ** 2).mean() + 0.01 * aux

        # jitted: un-jitted, the region and the reference's grad dispatch
        # op by op over the eight devices
        l_ref, g_ref = jax.jit(jax.value_and_grad(loss_ref))(params)

        specs = {"gate": {"wg": P()},
                 "experts": jax.tree.map(
                     lambda sp: P(None, *sp),
                     ExpertMLP.tp_partition_specs("model"),
                     is_leaf=lambda v: isinstance(v, P))}

        def region(pl, xl):
            def f(pp):
                y, aux, _ = layer.apply(pp, xl, tp_axis="model")
                return (y.astype(jnp.float32) ** 2).mean() + 0.01 * aux
            return jax.value_and_grad(f)(pl)

        fn = jax.jit(jax.shard_map(
            region, mesh=ctx.mesh, in_specs=(specs, P()),
            out_specs=(P(), specs), check_vma=False))
        l_tp, g_tp = fn(params, x)
        np.testing.assert_allclose(float(l_tp), float(l_ref), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_tp)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=2e-5)
        deepspeed_tpu.reset_mesh_context()


class TestRoutingStats:
    """ISSUE-15 satellite: gating drop accounting — exp_counts and
    RoutingStats reflect POST-capacity-mask reality (a token dropped by
    `locations < capacity` never counts as routed; its demand survives
    in overflow_counts)."""

    def _hot_logits(self, s=16, hot=0):
        # every token prefers expert `hot` decisively
        cols = [jnp.full((s,), 5.0) if e == hot else jnp.zeros(s)
                for e in range(E)]
        return jnp.stack(cols, axis=1)

    def test_top1_post_capacity_counts_and_overflow(self):
        logits = self._hot_logits()
        _, _, dispatch, counts, st = top1gating(
            logits, capacity_factor=0.5, min_capacity=2)  # capacity 2
        # routed == what the dispatch mask actually dispatched
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(dispatch.sum(axis=(0, 2))))
        assert float(counts[0]) == 2.0          # post-capacity, not 16
        assert float(st.expert_counts[0]) == 2.0
        assert float(st.overflow_counts[0]) == 14.0
        assert float(st.tokens) == 16.0
        assert float(st.dropped) == 14.0
        assert float(st.layers) == 1.0

    def test_top1_ample_capacity_zero_drops(self):
        logits = jax.random.normal(jax.random.PRNGKey(11), (16, E))
        _, _, dispatch, counts, st = top1gating(
            logits, capacity_factor=float(E), min_capacity=16)
        assert float(st.dropped) == 0.0
        assert float(st.tokens) == 16.0
        np.testing.assert_array_equal(np.asarray(st.overflow_counts),
                                      np.zeros(E))
        np.testing.assert_array_equal(np.asarray(st.expert_counts),
                                      np.asarray(dispatch.sum(axis=(0, 2))))

    def test_top1_used_token_masks_everything(self):
        logits = self._hot_logits()
        used = jnp.asarray([1.0] * 8 + [0.0] * 8)
        _, _, dispatch, counts, st = top1gating(
            logits, capacity_factor=float(E), min_capacity=16,
            used_token=used)
        # padding tokens neither want nor route nor contribute entropy
        assert float(st.tokens) == 8.0
        assert float(st.dropped) == 0.0
        assert float(st.gate_tokens) == 8.0
        assert float(counts.sum()) == 8.0
        # and with a tight capacity the drop accounting still holds
        _, _, _, counts2, st2 = top1gating(
            logits, capacity_factor=0.5, min_capacity=2, used_token=used)
        assert float(counts2[0]) == 2.0
        assert float(st2.dropped) == 6.0
        assert float(st2.overflow_counts[0]) == 6.0

    def test_top2_doubled_capacity_in_overflow(self):
        s = 16
        logits = self._hot_logits(s)
        (_, cap, _, _, _, counts, st) = (
            __import__("deepspeed_tpu.moe.sharded_moe",
                       fromlist=["top2gating_compact"]).top2gating_compact(
                logits, capacity_factor=1.0, min_capacity=1))
        # top-2 doubles the slot budget: ceil(16/4 * 2 * 1.0) = 8
        assert cap == 8
        # expert 0 wanted by all 16 first choices, keeps the DOUBLED
        # capacity's 8; the second choice (argmax ties -> expert 1)
        # absorbs 16 wants against the same budget
        assert float(st.expert_counts[0]) == 8.0
        assert float(st.overflow_counts[0]) == 8.0
        assert float(st.tokens) == 2.0 * s      # k=2 slots per token
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(st.expert_counts))

    def test_top2_post_capacity_matches_dispatch(self):
        logits = jax.random.normal(jax.random.PRNGKey(12), (32, E))
        _, _, dispatch, counts, st = top2gating(
            logits, capacity_factor=0.25, min_capacity=1)
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(dispatch.sum(axis=(0, 2))))
        np.testing.assert_array_equal(np.asarray(st.expert_counts),
                                      np.asarray(counts))
        assert float(st.tokens) == float(
            st.expert_counts.sum() + st.overflow_counts.sum())

    def test_entropy_normalization_bounds(self):
        # uniform router -> per-token entropy == ln(E); peaked -> ~0
        from deepspeed_tpu.moe.sharded_moe import top1gating_compact
        s = 32
        uniform = top1gating_compact(jnp.zeros((s, E)),
                                     capacity_factor=float(E),
                                     min_capacity=s)[-1]
        assert float(uniform.entropy) == pytest.approx(s * np.log(E),
                                                       rel=1e-5)
        peaked = top1gating_compact(self._hot_logits(s) * 10.0,
                                    capacity_factor=float(E),
                                    min_capacity=s)[-1]
        assert float(peaked.entropy) < 0.05 * s * np.log(E)
        # confidence: uniform top-1 mass is 1/E per token, peaked ~ 1
        assert float(uniform.confidence) == pytest.approx(s / E, rel=1e-5)
        assert float(peaked.confidence) > 0.95 * s

    def test_tap_collects_and_sums_across_layers(self):
        from deepspeed_tpu.moe import (collect_routing_stats,
                                       sum_routing_stats)
        gate = TopKGate(D, E, k=1, capacity_factor=float(E),
                        min_capacity=64)
        layer = MOELayer(gate, ExpertMLP(D), E)
        x = jax.random.normal(jax.random.PRNGKey(13), (16, D))
        params = layer.init_params(jax.random.PRNGKey(14), x)
        with collect_routing_stats() as tap:
            layer.apply(params, x, train=False)
            layer.apply(params, x, train=False)
        assert len(tap) == 2
        total = sum_routing_stats(tap)
        assert float(total.layers) == 2.0
        assert float(total.tokens) == 32.0
        # outside the context, emissions go nowhere
        layer.apply(params, x, train=False)
        assert len(tap) == 2
        assert sum_routing_stats([]) is None


class TestMeshValidationMessage:
    def test_error_names_axis_sizes_and_nearest_valid_counts(self):
        """ISSUE-15 satellite: the num_experts-vs-expert-axis failure
        names both values and the nearest valid expert counts."""
        deepspeed_tpu.initialize_mesh(expert=4, data=-1)
        with pytest.raises(ValueError) as ei:
            MoE(hidden_size=D, num_experts=6)
        msg = str(ei.value)
        assert "num_experts=6" in msg
        assert "expert=4" in msg
        assert "4 or 8" in msg           # nearest multiples of ep_size
        assert "divisor of 6" in msg
        deepspeed_tpu.reset_mesh_context()

    def test_error_below_ep_size_suggests_ep_size(self):
        deepspeed_tpu.initialize_mesh(expert=4, data=-1)
        with pytest.raises(ValueError) as ei:
            MoE(hidden_size=D, num_experts=2)
        # below=0 is not a valid expert count; only ep_size survives
        assert "Nearest valid num_experts: 4;" in str(ei.value)
        deepspeed_tpu.reset_mesh_context()
