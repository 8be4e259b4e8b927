"""Bidirectional (encoder / BERT-class) models: attention directionality,
MLM masking, loss, and a short training-improves test.

Analog of the reference's BERT-base pretraining config ("Ray Train:
GPT-2-small / BERT-base data-parallel JaxTrainer"): the same
transformer blocks run with causal=False and the MLM objective.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.config import bert_base_config, tiny_config
from ray_tpu.models.mlm import mask_tokens
from ray_tpu.models.transformer import forward, init_params, loss_fn


def _tiny_encoder(**kw):
    return dataclasses.replace(
        tiny_config(dtype=jnp.float32, param_dtype=jnp.float32),
        causal=False, **kw)


class TestBidirectionalAttention:
    def test_late_token_influences_early_logits(self):
        """causal=False: flipping the LAST input token must change the
        FIRST position's logits; causal=True: it must not."""
        enc = _tiny_encoder()
        dec = dataclasses.replace(enc, causal=True)
        params = init_params(jax.random.key(0), enc)
        a = jnp.asarray([[5, 6, 7, 8]], jnp.int32)
        b = jnp.asarray([[5, 6, 7, 9]], jnp.int32)
        enc_a = np.asarray(forward(params, a, enc)[0, 0])
        enc_b = np.asarray(forward(params, b, enc)[0, 0])
        assert not np.allclose(enc_a, enc_b)
        dec_a = np.asarray(forward(params, a, dec)[0, 0])
        dec_b = np.asarray(forward(params, b, dec)[0, 0])
        np.testing.assert_allclose(dec_a, dec_b, atol=1e-5)

    def test_bert_base_preset_geometry(self):
        cfg = bert_base_config()
        assert not cfg.causal and cfg.tie_embeddings
        assert 100e6 < cfg.num_params < 130e6  # 110M class


class TestMLM:
    def test_mask_tokens_shapes_and_recipe(self):
        rng = np.random.default_rng(0)
        toks = rng.integers(5, 1000, size=(4, 128))
        out = mask_tokens(toks, mask_id=3, vocab_size=1000,
                          rng=np.random.default_rng(1))
        assert out["inputs"].shape == toks.shape
        np.testing.assert_array_equal(out["targets"], toks)
        sel = out["mask"].astype(bool)
        frac = sel.mean()
        assert 0.10 < frac < 0.20  # ~15%
        # unmasked positions pass through unchanged
        np.testing.assert_array_equal(out["inputs"][~sel], toks[~sel])
        # ~80% of selected positions became [MASK]
        mask_frac = (out["inputs"][sel] == 3).mean()
        assert 0.6 < mask_frac < 0.95
        # every row predicts something
        assert sel.any(axis=1).all()

    def test_special_ids_never_selected(self):
        toks = np.full((2, 64), 7)
        toks[:, 0] = 101  # [CLS]-style special token
        out = mask_tokens(toks, mask_id=3, vocab_size=1000,
                          special_ids=(101,),
                          rng=np.random.default_rng(2))
        assert out["mask"][:, 0].sum() == 0

    def test_mlm_training_reduces_loss(self):
        """A few Adam steps on a fixed batch must cut the MLM loss —
        exercises the full encoder path end-to-end."""
        import optax

        cfg = _tiny_encoder(remat=False)
        params = init_params(jax.random.key(0), cfg)
        rng = np.random.default_rng(0)
        toks = rng.integers(4, cfg.vocab_size, size=(8, 32))
        batch = mask_tokens(toks, mask_id=3, vocab_size=cfg.vocab_size,
                            rng=rng)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        opt = optax.adam(1e-3)
        opt_state = opt.init(params)

        @jax.jit
        def step(params, opt_state):
            (loss, _), grads = jax.value_and_grad(
                lambda p: loss_fn(p, batch, cfg), has_aux=True)(params)
            upd, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, upd), opt_state, loss

        losses = []
        for _ in range(25):
            params, opt_state, loss = step(params, opt_state)
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.7, losses[:3] + losses[-3:]


class TestEncoderTrain:
    @pytest.fixture
    def runtime(self):
        import ray_tpu

        ray_tpu.init(num_cpus=4, num_tpus=0)
        yield
        ray_tpu.shutdown()

    def test_bert_style_jax_trainer(self, runtime, tmp_path):
        """The reference's "BERT-base data-parallel JaxTrainer" config shape:
        an MLM encoder loop under the Train gang (scaled tiny)."""
        from ray_tpu import train
        from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

        def loop(config):
            import dataclasses

            import jax
            import jax.numpy as jnp
            import numpy as np
            import optax

            from ray_tpu.models.config import tiny_config
            from ray_tpu.models.mlm import mask_tokens
            from ray_tpu.models.transformer import init_params, loss_fn
            from ray_tpu.train import session

            cfg = dataclasses.replace(
                tiny_config(dtype=jnp.float32, param_dtype=jnp.float32),
                causal=False, remat=False)
            params = init_params(jax.random.key(0), cfg)
            opt = optax.adam(1e-3)
            opt_state = opt.init(params)
            rng = np.random.default_rng(session.get_world_rank())
            toks = rng.integers(4, cfg.vocab_size, size=(8, 32))
            batch = {k: jnp.asarray(v) for k, v in mask_tokens(
                toks, mask_id=3, vocab_size=cfg.vocab_size,
                rng=rng).items()}

            @jax.jit
            def step(params, opt_state):
                (loss, _), grads = jax.value_and_grad(
                    lambda p: loss_fn(p, batch, cfg),
                    has_aux=True)(params)
                upd, opt_state = opt.update(grads, opt_state, params)
                return optax.apply_updates(params, upd), opt_state, loss

            for _ in range(10):
                params, opt_state, loss = step(params, opt_state)
                train.report({"mlm_loss": float(loss)})

        result = JaxTrainer(
            loop,
            scaling_config=ScalingConfig(num_workers=2),
            run_config=RunConfig(name="bert_mlm",
                                 storage_path=str(tmp_path)),
        ).fit()
        assert result.error is None
        losses = [m["mlm_loss"] for m in result.metrics_history]
        assert losses[-1] < losses[0]


class TestEncoderSharded:
    def test_encoder_runs_on_mesh(self):
        """Bidirectional attention through the sharded path (ring
        attention's causal=False branch on a sequence-sharded mesh)."""
        from ray_tpu.parallel import make_mesh

        if len(jax.devices()) < 4:
            pytest.skip("needs the 8-device CPU mesh")
        mesh = make_mesh(data=2, sequence=2, fsdp=1)
        cfg = _tiny_encoder(attention_impl="ring", remat=False)
        params = init_params(jax.random.key(0), cfg)
        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)),
                           jnp.int32)
        out = jax.jit(
            lambda p, t: forward(p, t, cfg, mesh))(params, toks)
        assert out.shape == (2, 16, cfg.vocab_size)
        # parity vs the unsharded xla path
        ref = forward(params, toks, dataclasses.replace(
            cfg, attention_impl="xla"))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-3)
