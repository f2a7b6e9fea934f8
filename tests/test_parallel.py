"""Distributed-layer tests on the 8-device virtual CPU mesh:
ring attention numerics vs dense, mesh factoring, sharded LM train step
(dp/fsdp/tp/sp), gradient flow through the ring.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from katib_tpu.ops.ring_attention import dense_attention, ring_attention
from katib_tpu.parallel.mesh import make_mesh, mesh_axis_sizes


@pytest.fixture(scope="module")
def devices():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs


class TestMesh:
    def test_factoring(self, devices):
        mesh = make_mesh(devices, model=2, seq=2)
        sizes = mesh_axis_sizes(mesh)
        assert sizes["model"] == 2 and sizes["seq"] == 2 and sizes["data"] == 2

    def test_bad_factoring(self, devices):
        with pytest.raises(ValueError):
            make_mesh(devices, model=3)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.smoke
    def test_matches_dense(self, devices, causal):
        mesh = make_mesh(devices, seq=4)  # data=2, seq=4
        rng = np.random.default_rng(0)
        b, t, h, d = 2, 32, 4, 8
        q = jnp.asarray(rng.standard_normal((b, t, h, d)), dtype=jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, t, h, d)), dtype=jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, t, h, d)), dtype=jnp.float32)

        expected = dense_attention(q, k, v, causal=causal)
        with mesh:
            got = ring_attention(q, k, v, mesh, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5, rtol=2e-5)

    def test_differentiable(self, devices):
        mesh = make_mesh(devices, seq=4)
        rng = np.random.default_rng(1)
        b, t, h, d = 2, 16, 2, 4
        q = jnp.asarray(rng.standard_normal((b, t, h, d)), dtype=jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, t, h, d)), dtype=jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, t, h, d)), dtype=jnp.float32)

        def ring_loss(q, k, v):
            with mesh:
                return ring_attention(q, k, v, mesh, causal=True).sum()

        def dense_loss(q, k, v):
            return dense_attention(q, k, v, causal=True).sum()

        g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
        g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
        for gr, gd in zip(g_ring, g_dense):
            np.testing.assert_allclose(np.asarray(gr), np.asarray(gd), atol=2e-4, rtol=2e-4)

    def test_single_shard_fallback(self, devices):
        mesh = make_mesh(devices)  # seq=1 -> dense path
        q = jnp.ones((2, 8, 2, 4))
        out = ring_attention(q, q, q, mesh, causal=False)
        assert out.shape == q.shape


class TestShardedTrainStep:
    @pytest.mark.smoke
    def test_dp_tp_sp_step_runs_and_learns(self, devices):
        from katib_tpu.models.transformer import TransformerConfig
        from katib_tpu.parallel.train import make_lm_train_step

        mesh = make_mesh(devices, model=2, seq=2)  # data=2, model=2, seq=2
        config = TransformerConfig(
            vocab_size=64, embed_dim=32, num_layers=2, num_heads=2, max_seq_len=32,
            dtype=jnp.float32,
        )
        params, opt_state, step_fn, put_batch = make_lm_train_step(config, mesh, 1e-2)
        rng = np.random.default_rng(0)
        data = rng.integers(0, 64, size=(4, 33), dtype=np.int32)
        losses = []
        for _ in range(10):
            tokens, targets, positions = put_batch(data[:, :-1], data[:, 1:])
            params, opt_state, loss = step_fn(params, opt_state, tokens, targets, positions)
            losses.append(float(loss))
        assert losses[-1] < losses[0]  # memorizes the repeated batch
        # params actually sharded over the mesh
        import flax

        flat = flax.traverse_util.flatten_dict(params)
        qkv = [v for k, v in flat.items() if "qkv" in k][0]
        assert len(qkv.sharding.device_set) == 8

    def test_single_device_mesh_skips_gspmd(self, devices):
        """A 1-device mesh must build the plain-jit step (no NamedSharding):
        GSPMD partitioning buys nothing on one chip."""
        from jax.sharding import SingleDeviceSharding

        from katib_tpu.models.transformer import TransformerConfig
        from katib_tpu.parallel.train import make_lm_train_step

        mesh = make_mesh(devices[:1])
        config = TransformerConfig(
            vocab_size=64, embed_dim=32, num_layers=1, num_heads=2,
            max_seq_len=16, dtype=jnp.float32,
        )
        params, opt_state, step_fn, put_batch = make_lm_train_step(config, mesh, 1e-2)
        import flax

        leaf = next(iter(flax.traverse_util.flatten_dict(params).values()))
        assert isinstance(leaf.sharding, SingleDeviceSharding)
        rng = np.random.default_rng(0)
        data = rng.integers(0, 64, size=(2, 17), dtype=np.int32)
        tokens, targets, positions = put_batch(data[:, :-1], data[:, 1:])
        assert isinstance(tokens.sharding, SingleDeviceSharding)
        params, opt_state, loss = step_fn(params, opt_state, tokens, targets, positions)
        assert np.isfinite(float(loss))

    def test_single_device_mesh_nondefault_chip_placement(self, devices):
        """A 1-device mesh on chip k != 0 must still place params/batches and
        run the step on that chip (via jax.default_device, not committed
        device_put — see the placement note in make_lm_train_step)."""
        from katib_tpu.models.transformer import TransformerConfig
        from katib_tpu.parallel.train import make_lm_train_step

        target = devices[3]
        mesh = make_mesh([target])
        config = TransformerConfig(
            vocab_size=64, embed_dim=32, num_layers=1, num_heads=2,
            max_seq_len=16, dtype=jnp.float32,
        )
        params, opt_state, step_fn, put_batch = make_lm_train_step(config, mesh, 1e-2)
        import flax

        leaf = next(iter(flax.traverse_util.flatten_dict(params).values()))
        assert leaf.devices() == {target}
        opt_leaf = next(
            x for x in jax.tree_util.tree_leaves(opt_state) if hasattr(x, "devices")
        )
        assert opt_leaf.devices() == {target}
        rng = np.random.default_rng(0)
        data = rng.integers(0, 64, size=(2, 17), dtype=np.int32)
        tokens, targets, positions = put_batch(data[:, :-1], data[:, 1:])
        assert tokens.devices() == {target}
        params, opt_state, loss = step_fn(params, opt_state, tokens, targets, positions)
        assert loss.devices() == {target}
        assert np.isfinite(float(loss))

    def test_run_lm_trial_entry(self, devices):
        from katib_tpu.parallel.train import run_lm_trial

        # entry-point smoke: dp-only tiny run without a ctx
        run_lm_trial(
            {
                "learning_rate": "1e-3", "embed_dim": "16", "num_layers": "1",
                "num_heads": "2", "num_steps": "2", "batch_size": "8",
                "seq_len": "16", "vocab_size": "32",
            }
        )


class TestMoEExpertParallel:
    """Expert parallelism: top-1 routed MoE with experts over the 'expert'
    mesh axis (token all-to-all inserted by XLA at the sharding constraint)."""

    def test_moe_step_runs_and_learns(self, devices):
        from katib_tpu.models.transformer import TransformerConfig
        from katib_tpu.parallel.train import make_lm_train_step

        cfg = TransformerConfig(
            vocab_size=64, embed_dim=32, num_layers=2, num_heads=2,
            max_seq_len=16, dtype=jnp.float32, num_experts=4,
        )
        mesh = make_mesh(devices, expert=2, data=2, fsdp=2)
        params, opt_state, step_fn, put_batch = make_lm_train_step(cfg, mesh, 1e-2)
        rng = np.random.default_rng(0)
        data = rng.integers(0, 64, size=(8, 17), dtype=np.int32)
        losses = []
        for _ in range(6):
            tokens, targets, positions = put_batch(data[:, :-1], data[:, 1:])
            params, opt_state, loss = step_fn(params, opt_state, tokens, targets, positions)
            losses.append(float(loss))
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0]

    def test_expert_weights_sharded(self, devices):
        import flax
        from katib_tpu.models.transformer import TransformerConfig, param_sharding_rules
        from jax.sharding import PartitionSpec as P

        assert param_sharding_rules(("block0", "moe", "w_in")) == P("expert", "fsdp", "model")
        assert param_sharding_rules(("block0", "moe", "w_out")) == P("expert", "model", "fsdp")


@pytest.mark.heavy  # one pipeline compile per composition (~4 min total)
class TestPipelineParallel:
    """GPipe microbatch pipeline over 'pipe' (ppermute rotation, backward
    schedule via autodiff of the scanned forward)."""

    def _setup(self, devices, n_micro=4):
        from katib_tpu.models.transformer import TransformerConfig
        from katib_tpu.parallel.pipeline import make_pipeline_lm_train_step

        cfg = TransformerConfig(
            vocab_size=64, embed_dim=32, num_layers=4, num_heads=2,
            max_seq_len=16, dtype=jnp.float32,
        )
        mesh = make_mesh(devices, pipe=2, model=1, seq=1)  # pipe=2, data=4
        return cfg, mesh, make_pipeline_lm_train_step(cfg, mesh, 1e-3, num_microbatches=n_micro)

    def test_matches_unpipelined_forward(self, devices):
        """Pipeline loss == sequential layer application with same params."""
        import optax
        from katib_tpu.models.transformer import Block, RMSNorm

        cfg, mesh, (params, opt_state, step_fn, put_batch) = self._setup(devices)
        rng = np.random.default_rng(0)
        B, T = 16, 16
        data = rng.integers(0, 64, size=(B, T + 1), dtype=np.int32)
        tokens, targets = put_batch(data[:, :-1], data[:, 1:])

        block = Block(cfg, mesh=None)
        emb = np.asarray(params["embed"])
        blocks = jax.tree.map(np.asarray, params["blocks"])
        x = jnp.asarray(emb[data[:, :-1]])
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        for s in range(2):
            for l in range(2):
                lp = jax.tree.map(lambda a: a[s, l], blocks)
                x = block.apply({"params": lp}, x, pos)
        h = RMSNorm().apply({"params": {"scale": np.asarray(params["ln_f"])}}, x)
        logits = jnp.einsum("bte,ve->btv", h, jnp.asarray(emb))
        ref = float(
            optax.softmax_cross_entropy_with_integer_labels(
                logits, jnp.asarray(data[:, 1:])
            ).mean()
        )
        _, _, loss = step_fn(params, opt_state, tokens, targets)
        assert abs(float(loss) - ref) < 1e-4

    def test_pipeline_learns(self, devices):
        cfg, mesh, (params, opt_state, step_fn, put_batch) = self._setup(devices)
        rng = np.random.default_rng(1)
        data = rng.integers(0, 64, size=(16, 17), dtype=np.int32)
        losses = []
        for _ in range(6):
            tokens, targets = put_batch(data[:, :-1], data[:, 1:])
            params, opt_state, loss = step_fn(params, opt_state, tokens, targets)
            losses.append(float(loss))
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0]

    def test_rejects_bad_mesh(self, devices):
        from katib_tpu.models.transformer import TransformerConfig
        from katib_tpu.parallel.pipeline import make_pipeline_lm_train_step

        cfg = TransformerConfig(vocab_size=64, embed_dim=32, num_layers=4, num_heads=2)
        mesh = make_mesh(devices, model=2)  # pipe=1
        with pytest.raises(ValueError):
            make_pipeline_lm_train_step(cfg, mesh)
        mesh2 = make_mesh(devices, pipe=2, expert=2)
        cfg_moe = TransformerConfig(
            vocab_size=64, embed_dim=32, num_layers=4, num_heads=2,
            num_experts=3,  # not divisible by expert=2
        )
        with pytest.raises(ValueError):
            make_pipeline_lm_train_step(cfg_moe, mesh2)

    def _setup_tp(self, devices, n_micro=4):
        """pipe=2 x model=2 x data=2: TP inside each stage (auto/GSPMD over
        'model' within the manual pipe/data shard_map)."""
        from katib_tpu.models.transformer import TransformerConfig
        from katib_tpu.parallel.pipeline import make_pipeline_lm_train_step

        cfg = TransformerConfig(
            vocab_size=64, embed_dim=32, num_layers=4, num_heads=2,
            max_seq_len=16, dtype=jnp.float32,
        )
        mesh = make_mesh(devices, pipe=2, model=2)  # data absorbs to 2
        return cfg, mesh, make_pipeline_lm_train_step(cfg, mesh, 1e-3, num_microbatches=n_micro)

    def test_pp_tp_matches_unpipelined_forward(self, devices):
        """pp x tp x dp loss == sequential single-device application."""
        import optax
        from katib_tpu.models.transformer import Block, RMSNorm

        cfg, mesh, (params, opt_state, step_fn, put_batch) = self._setup_tp(devices)
        rng = np.random.default_rng(0)
        B, T = 8, 16
        data = rng.integers(0, 64, size=(B, T + 1), dtype=np.int32)
        tokens, targets = put_batch(data[:, :-1], data[:, 1:])

        block = Block(cfg, mesh=None)
        emb = np.asarray(params["embed"])
        blocks = jax.tree.map(np.asarray, params["blocks"])
        x = jnp.asarray(emb[data[:, :-1]])
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        for s in range(2):
            for l in range(2):
                lp = jax.tree.map(lambda a: a[s, l], blocks)
                x = block.apply({"params": lp}, x, pos)
        h = RMSNorm().apply({"params": {"scale": np.asarray(params["ln_f"])}}, x)
        logits = jnp.einsum("bte,ve->btv", h, jnp.asarray(emb))
        ref = float(
            optax.softmax_cross_entropy_with_integer_labels(
                logits, jnp.asarray(data[:, 1:])
            ).mean()
        )
        _, _, loss = step_fn(params, opt_state, tokens, targets)
        assert abs(float(loss) - ref) < 1e-4

    def test_pp_tp_learns_and_keeps_tp_sharding(self, devices):
        cfg, mesh, (params, opt_state, step_fn, put_batch) = self._setup_tp(devices)
        # stage qkv kernels really are TP-sharded over 'model'
        qkv = params["blocks"]["attn"]["qkv"]["kernel"]
        assert "model" in tuple(qkv.sharding.spec), qkv.sharding.spec
        rng = np.random.default_rng(1)
        data = rng.integers(0, 64, size=(8, 17), dtype=np.int32)
        losses = []
        for _ in range(6):
            tokens, targets = put_batch(data[:, :-1], data[:, 1:])
            params, opt_state, loss = step_fn(params, opt_state, tokens, targets)
            losses.append(float(loss))
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0]

    def _setup_fsdp(self, devices, n_micro=4):
        """pipe=2 x fsdp=2 x data=2: ZeRO within each stage — stage weights
        and optimizer state sharded over 'fsdp' (an auto/GSPMD axis inside
        the manual pipe/data shard_map), gathered at compute."""
        from katib_tpu.models.transformer import TransformerConfig
        from katib_tpu.parallel.pipeline import make_pipeline_lm_train_step

        cfg = TransformerConfig(
            vocab_size=64, embed_dim=32, num_layers=4, num_heads=2,
            max_seq_len=16, dtype=jnp.float32,
        )
        mesh = make_mesh(devices, pipe=2, fsdp=2)  # data absorbs to 2
        return cfg, mesh, make_pipeline_lm_train_step(cfg, mesh, 1e-3, num_microbatches=n_micro)

    def test_pp_fsdp_matches_unpipelined_forward(self, devices):
        """pp x fsdp x dp loss == sequential single-device application."""
        import optax
        from katib_tpu.models.transformer import Block, RMSNorm

        cfg, mesh, (params, opt_state, step_fn, put_batch) = self._setup_fsdp(devices)
        rng = np.random.default_rng(0)
        B, T = 8, 16
        data = rng.integers(0, 64, size=(B, T + 1), dtype=np.int32)
        tokens, targets = put_batch(data[:, :-1], data[:, 1:])

        block = Block(cfg, mesh=None)
        emb = np.asarray(params["embed"])
        blocks = jax.tree.map(np.asarray, params["blocks"])
        x = jnp.asarray(emb[data[:, :-1]])
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        for s in range(2):
            for l in range(2):
                lp = jax.tree.map(lambda a: a[s, l], blocks)
                x = block.apply({"params": lp}, x, pos)
        h = RMSNorm().apply({"params": {"scale": np.asarray(params["ln_f"])}}, x)
        logits = jnp.einsum("bte,ve->btv", h, jnp.asarray(emb))
        ref = float(
            optax.softmax_cross_entropy_with_integer_labels(
                logits, jnp.asarray(data[:, 1:])
            ).mean()
        )
        _, _, loss = step_fn(params, opt_state, tokens, targets)
        assert abs(float(loss) - ref) < 1e-4

    def _setup_sp(self, devices, n_micro=2):
        """pipe=2 x seq=2 x data=2: ring attention inside each stage (the
        shard_map is manual over 'seq' too; Attention.seq_axis runs
        ring_attention_local over it with rank-offset global positions)."""
        from katib_tpu.models.transformer import TransformerConfig
        from katib_tpu.parallel.pipeline import make_pipeline_lm_train_step

        cfg = TransformerConfig(
            vocab_size=64, embed_dim=32, num_layers=2, num_heads=2,
            max_seq_len=32, dtype=jnp.float32,
        )
        mesh = make_mesh(devices, pipe=2, seq=2)  # data absorbs to 2
        return cfg, mesh, make_pipeline_lm_train_step(cfg, mesh, 1e-3, num_microbatches=n_micro)

    def test_pp_sp_matches_unpipelined_forward(self, devices):
        """pp x sp x dp loss == sequential single-device application — the
        ring schedule's cross-shard causality and RoPE offsets are exact."""
        import optax
        from katib_tpu.models.transformer import Block, RMSNorm

        cfg, mesh, (params, opt_state, step_fn, put_batch) = self._setup_sp(devices)
        rng = np.random.default_rng(0)
        B, T = 8, 32
        data = rng.integers(0, 64, size=(B, T + 1), dtype=np.int32)
        tokens, targets = put_batch(data[:, :-1], data[:, 1:])

        block = Block(cfg, mesh=None)
        emb = np.asarray(params["embed"])
        blocks = jax.tree.map(np.asarray, params["blocks"])
        x = jnp.asarray(emb[data[:, :-1]])
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        for s in range(2):
            lp = jax.tree.map(lambda a: a[s, 0], blocks)
            x = block.apply({"params": lp}, x, pos)
        h = RMSNorm().apply({"params": {"scale": np.asarray(params["ln_f"])}}, x)
        logits = jnp.einsum("bte,ve->btv", h, jnp.asarray(emb))
        ref = float(
            optax.softmax_cross_entropy_with_integer_labels(
                logits, jnp.asarray(data[:, 1:])
            ).mean()
        )
        _, _, loss = step_fn(params, opt_state, tokens, targets)
        assert abs(float(loss) - ref) < 1e-4

    def test_pp_sp_learns(self, devices):
        cfg, mesh, (params, opt_state, step_fn, put_batch) = self._setup_sp(devices)
        rng = np.random.default_rng(1)
        data = rng.integers(0, 64, size=(8, 33), dtype=np.int32)
        tokens, targets = put_batch(data[:, :-1], data[:, 1:])
        # tokens really are sequence-sharded at the input
        assert not tokens.sharding.is_fully_replicated
        losses = []
        for _ in range(6):
            params, opt_state, loss = step_fn(params, opt_state, tokens, targets)
            losses.append(float(loss))
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0]

    def _setup_ep(self, devices, aux_weight=0.0, n_micro=2):
        """pipe=2 x expert=2 x data=2: MoE inside each stage — the shard_map
        is manual over 'expert' too, each device's stage holds
        num_experts/2 expert FFNs, and MoE.expert_axis exchanges tokens for
        experts with a direct all_to_all."""
        from katib_tpu.models.transformer import TransformerConfig
        from katib_tpu.parallel.pipeline import make_pipeline_lm_train_step

        cfg = TransformerConfig(
            vocab_size=64, embed_dim=32, num_layers=2, num_heads=2,
            max_seq_len=16, dtype=jnp.float32, num_experts=4,
            moe_aux_weight=aux_weight,
        )
        mesh = make_mesh(devices, pipe=2, expert=2)  # data absorbs to 2
        return cfg, mesh, make_pipeline_lm_train_step(cfg, mesh, 1e-3, num_microbatches=n_micro)

    def test_pp_ep_matches_unpipelined_forward(self, devices):
        """pp x ep x dp CE == sequential single-device application (aux off:
        the load-balance statistic is per-shard by design, but the routed
        compute itself must be exact through the all_to_all exchange)."""
        import optax
        from katib_tpu.models.transformer import Block, RMSNorm

        cfg, mesh, (params, opt_state, step_fn, put_batch) = self._setup_ep(devices)
        # MoE stage weights really are expert-sharded at their local shape
        w_in = params["blocks"]["moe"]["w_in"]
        assert "expert" in jax.tree_util.tree_leaves(tuple(w_in.sharding.spec))
        rng = np.random.default_rng(0)
        B, T = 8, 16
        data = rng.integers(0, 64, size=(B, T + 1), dtype=np.int32)
        tokens, targets = put_batch(data[:, :-1], data[:, 1:])

        block = Block(cfg, mesh=None)
        emb = np.asarray(params["embed"])
        blocks = jax.tree.map(np.asarray, params["blocks"])
        x = jnp.asarray(emb[data[:, :-1]])
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        for s in range(2):
            lp = jax.tree.map(lambda a: a[s, 0], blocks)
            x = block.apply({"params": lp}, x, pos)
        h = RMSNorm().apply({"params": {"scale": np.asarray(params["ln_f"])}}, x)
        logits = jnp.einsum("bte,ve->btv", h, jnp.asarray(emb))
        ref = float(
            optax.softmax_cross_entropy_with_integer_labels(
                logits, jnp.asarray(data[:, 1:])
            ).mean()
        )
        _, _, loss = step_fn(params, opt_state, tokens, targets)
        assert abs(float(loss) - ref) < 1e-4

    def test_pp_ep_expert_grad_scale_matches_unsharded(self, devices):
        """One plain-SGD step must move the expert FFN weights identically
        whether experts are sharded (pp x ep x dp) or not (pp x dp) — the
        a2a transpose accumulates expert_par device losses into each
        shard's gradient, which must be rescaled to the mean-loss gradient
        (Adam's scale-invariance would mask this; SGD exposes it)."""
        import optax
        from katib_tpu.models.transformer import TransformerConfig
        from katib_tpu.parallel.pipeline import make_pipeline_lm_train_step

        cfg = TransformerConfig(
            vocab_size=64, embed_dim=32, num_layers=2, num_heads=2,
            max_seq_len=16, dtype=jnp.float32, num_experts=4,
            moe_aux_weight=0.0,
        )
        rng = np.random.default_rng(3)
        data = rng.integers(0, 64, size=(8, 17), dtype=np.int32)

        def one_step(mesh):
            params, opt, step_fn, put = make_pipeline_lm_train_step(
                cfg, mesh, num_microbatches=2, tx=optax.sgd(0.1)
            )
            t, tg = put(data[:, :-1], data[:, 1:])
            w0 = np.asarray(params["blocks"]["moe"]["w_in"])  # before donation
            p1, _, _ = step_fn(params, opt, t, tg)
            return np.asarray(p1["blocks"]["moe"]["w_in"]) - w0

        d_plain = one_step(make_mesh(devices, pipe=2))            # data=4
        d_ep = one_step(make_mesh(devices, pipe=2, expert=2))     # data=2,ep=2
        np.testing.assert_allclose(d_plain, d_ep, rtol=1e-4, atol=1e-7)

    def test_pp_ep_learns_with_aux(self, devices):
        cfg, mesh, (params, opt_state, step_fn, put_batch) = self._setup_ep(
            devices, aux_weight=1e-2
        )
        rng = np.random.default_rng(1)
        data = rng.integers(0, 64, size=(8, 17), dtype=np.int32)
        tokens, targets = put_batch(data[:, :-1], data[:, 1:])
        losses = []
        for _ in range(6):
            params, opt_state, loss = step_fn(params, opt_state, tokens, targets)
            losses.append(float(loss))
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0]

    def test_pp_fsdp_learns_and_keeps_fsdp_sharding(self, devices):
        cfg, mesh, (params, opt_state, step_fn, put_batch) = self._setup_fsdp(devices)
        # stage qkv kernels (and their Adam moments) really are ZeRO-sharded
        qkv = params["blocks"]["attn"]["qkv"]["kernel"]
        assert "fsdp" in jax.tree_util.tree_leaves(tuple(qkv.sharding.spec)), (
            qkv.sharding.spec
        )
        m_qkv = opt_state[0].mu["blocks"]["attn"]["qkv"]["kernel"]
        assert "fsdp" in jax.tree_util.tree_leaves(tuple(m_qkv.sharding.spec)), (
            m_qkv.sharding.spec
        )
        rng = np.random.default_rng(1)
        data = rng.integers(0, 64, size=(8, 17), dtype=np.int32)
        losses = []
        for _ in range(6):
            tokens, targets = put_batch(data[:, :-1], data[:, 1:])
            params, opt_state, loss = step_fn(params, opt_state, tokens, targets)
            losses.append(float(loss))
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0]


class TestTopologyMesh:
    def test_ctx_mesh_uses_topology_shape(self, devices):
        from katib_tpu.runtime.context import TrialContext

        ctx = TrialContext(
            trial_name="t", experiment_name="e", assignments={},
            reporter=None, devices=list(devices[:4]), topology="2x2",
        )
        mesh = ctx.mesh(axis_names=("data", "model"))
        assert mesh.devices.shape == (2, 2)
        # explicit shape still wins over topology
        mesh = ctx.mesh(axis_names=("data", "model"), shape=(4, 1))
        assert mesh.devices.shape == (4, 1)
        # 1-D default ignores topology
        assert ctx.mesh().devices.shape == (4,)

    def test_topology_validated_against_num_devices(self):
        from katib_tpu.api import (
            AlgorithmSpec, ExperimentSpec, FeasibleSpace, ObjectiveSpec,
            ObjectiveType, ParameterSpec, ParameterType, TrialResources,
            TrialTemplate, ValidationError, validate_experiment,
        )

        spec = ExperimentSpec(
            name="topo",
            parameters=[
                ParameterSpec("x", ParameterType.DOUBLE, FeasibleSpace(min="0", max="1")),
            ],
            objective=ObjectiveSpec(type=ObjectiveType.MAXIMIZE, objective_metric_name="s"),
            algorithm=AlgorithmSpec("random"),
            trial_template=TrialTemplate(
                entry_point="m:f",
                resources=TrialResources(num_devices=4, topology="2x3"),
            ),
            max_trial_count=1,
            parallel_trial_count=1,
        )
        with pytest.raises(ValidationError, match="multiplies to 6"):
            validate_experiment(spec, known_algorithms={"random"})
        spec.trial_template.resources.topology = "2x2"
        validate_experiment(spec, known_algorithms={"random"})


class TestPrefetch:
    """Device-prefetching input pipeline (katib_tpu.utils.prefetch)."""

    def test_prefetch_stages_and_preserves_order(self, devices):
        import numpy as onp

        from katib_tpu.utils.prefetch import prefetch_to_device

        src = [(onp.full((2, 2), i, dtype="float32"), onp.array([i])) for i in range(7)]
        out = list(prefetch_to_device(iter(src), size=3))
        assert len(out) == 7
        for i, (bx, by) in enumerate(out):
            assert isinstance(bx, jnp.ndarray)
            assert float(bx[0, 0]) == i and int(by[0]) == i

    def test_prefetch_with_sharding(self, devices):
        import numpy as onp

        from jax.sharding import NamedSharding, PartitionSpec as P

        from katib_tpu.utils.prefetch import prefetch_to_device

        mesh = make_mesh(devices)
        sharding = NamedSharding(mesh, P("data"))
        src = [onp.ones((8, 4), dtype="float32") for _ in range(3)]
        out = list(prefetch_to_device(iter(src), sharding=sharding))
        assert len(out) == 3
        assert out[0].sharding == sharding

    def test_prefetch_empty_and_short(self, devices):
        from katib_tpu.utils.prefetch import prefetch_to_device

        assert list(prefetch_to_device(iter([]))) == []
        assert len(list(prefetch_to_device(iter([jnp.ones(2)]), size=4))) == 1


class TestRingFlashKernelPath:
    """Force the Pallas kernel (interpret mode) inside the ring loop on the
    CPU mesh — the TPU-path plumbing (flash_attention_with_lse +
    merge_attention_blocks + flash_block_grads under shard_map/fori_loop/
    cond) that off-TPU defaults would otherwise never exercise."""

    def test_ring_with_kernel_blocks_matches_dense(self, devices, monkeypatch):
        import functools as ft

        from katib_tpu.ops import flash_attention as fa

        orig_lse = fa.flash_attention_with_lse
        monkeypatch.setattr(
            fa, "flash_attention_with_lse", ft.partial(orig_lse, interpret=True)
        )

        mesh = make_mesh(devices, seq=2)  # data=4, seq=2
        rng = np.random.default_rng(7)
        b, t, h, d = 4, 256, 2, 8  # t_local=128: kernel-eligible block
        q = jnp.asarray(rng.standard_normal((b, t, h, d)), dtype=jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, t, h, d)), dtype=jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, t, h, d)), dtype=jnp.float32)

        for causal in (False, True):
            expected = dense_attention(q, k, v, causal=causal)
            got = ring_attention(q, k, v, mesh, causal=causal)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(expected), atol=2e-5, rtol=2e-5
            )
