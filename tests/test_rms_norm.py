"""Fused-backward RMSNorm (kernels.rms_norm.rms_norm_train) parity.

The training stacks route their norms through rms_norm_train, whose
hand-written backward (Pallas on TPU, jnp twin elsewhere) must match
jax.grad of the reference formulation.
"""
import numpy as np
import pytest


class TestRmsNormTrain:
    def _setup(self):
        import jax.numpy as jnp
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(4, 6, 256) * 2.0, jnp.float32)
        w = jnp.asarray(1.0 + 0.1 * rng.randn(256), jnp.float32)
        return x, w

    @pytest.mark.parametrize("interpret", [False, True])
    def test_value_and_grads_match_ref(self, interpret):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.core import flags as F
        from paddle_tpu.kernels.rms_norm import rms_norm_ref, rms_norm_train
        x, w = self._setup()
        if interpret:
            F.set_flags({"FLAGS_pallas_interpret": True})
        try:
            out = rms_norm_train(x, w, 1e-6, True)
            np.testing.assert_allclose(np.asarray(out),
                                       np.asarray(rms_norm_ref(x, w, 1e-6)),
                                       rtol=1e-5, atol=1e-5)

            def loss_f(fn):
                return lambda x, w: jnp.sum(jnp.sin(fn(x, w)))

            gx, gw = jax.grad(
                loss_f(lambda x, w: rms_norm_train(x, w, 1e-6, True)),
                argnums=(0, 1))(x, w)
            gx_r, gw_r = jax.grad(
                loss_f(lambda x, w: rms_norm_ref(x, w, 1e-6)),
                argnums=(0, 1))(x, w)
            np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_r),
                                       rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_r),
                                       rtol=1e-4, atol=1e-4)
        finally:
            if interpret:
                F.set_flags({"FLAGS_pallas_interpret": False})

    def test_bf16_and_padded_rows(self):
        """Non-multiple-of-block row counts and bf16 inputs round-trip."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.core import flags as F
        from paddle_tpu.kernels.rms_norm import rms_norm_ref, rms_norm_train
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(3, 7, 128), jnp.bfloat16)
        w = jnp.asarray(1.0 + 0.1 * rng.randn(128), jnp.bfloat16)
        F.set_flags({"FLAGS_pallas_interpret": True})
        try:
            out = rms_norm_train(x, w, 1e-6, True)
            ref = rms_norm_ref(x, w, 1e-6)
            np.testing.assert_allclose(np.asarray(out, np.float32),
                                       np.asarray(ref, np.float32),
                                       rtol=2e-2, atol=2e-2)
            gx = jax.grad(lambda x: jnp.sum(
                rms_norm_train(x, w, 1e-6, True).astype(jnp.float32)))(x)
            gx_r = jax.grad(lambda x: jnp.sum(
                rms_norm_ref(x, w, 1e-6).astype(jnp.float32)))(x)
            np.testing.assert_allclose(np.asarray(gx, np.float32),
                                       np.asarray(gx_r, np.float32),
                                       rtol=5e-2, atol=5e-2)
        finally:
            F.set_flags({"FLAGS_pallas_interpret": False})


class TestLayerNormTrain:
    @pytest.mark.parametrize("affine", [True, False])
    @pytest.mark.parametrize("interpret", [False, True])
    def test_value_and_grads_match_ref(self, affine, interpret):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.core import flags as F
        from paddle_tpu.kernels.layer_norm import (layer_norm_ref,
                                                   layer_norm_train)
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(4, 6, 256) * 2.0, jnp.float32)
        w = jnp.asarray(1.0 + 0.1 * rng.randn(256),
                        jnp.float32) if affine else None
        b = jnp.asarray(0.1 * rng.randn(256),
                        jnp.float32) if affine else None
        if interpret:
            F.set_flags({"FLAGS_pallas_interpret": True})
        try:
            out = layer_norm_train(x, w, b, 1e-5, True)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(layer_norm_ref(x, w, b, 1e-5)),
                rtol=1e-5, atol=1e-5)

            if affine:
                def loss_t(x, w, b):
                    return jnp.sum(jnp.sin(layer_norm_train(x, w, b, 1e-5,
                                                            True)))

                def loss_r(x, w, b):
                    return jnp.sum(jnp.sin(layer_norm_ref(x, w, b, 1e-5)))

                gt = jax.grad(loss_t, argnums=(0, 1, 2))(x, w, b)
                gr = jax.grad(loss_r, argnums=(0, 1, 2))(x, w, b)
            else:
                gt = (jax.grad(lambda x: jnp.sum(jnp.sin(
                    layer_norm_train(x, None, None, 1e-5, True))))(x),)
                gr = (jax.grad(lambda x: jnp.sum(jnp.sin(
                    layer_norm_ref(x, None, None, 1e-5))))(x),)
            for a, r in zip(gt, gr):
                np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                           rtol=1e-4, atol=1e-4)
        finally:
            if interpret:
                F.set_flags({"FLAGS_pallas_interpret": False})


class TestRmsNormSharded:
    """rms_norm_train_sharded (VERDICT r4 next-3): the fused kernel under
    a mesh via shard_map — value/grad parity with the ref path."""

    def test_sharded_matches_ref(self):
        import numpy as np
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.core import flags
        from paddle_tpu.parallel.topology import build_mesh
        from paddle_tpu.kernels.rms_norm import (rms_norm_ref,
                                                 rms_norm_train_sharded)
        mesh = build_mesh(dp=2, sharding=2, mp=2)
        spec = P(("dp", "sharding"), None, None)
        x = jnp.asarray(np.random.RandomState(0).randn(8, 16, 128),
                        jnp.float32)
        w = jnp.asarray(np.random.RandomState(1).rand(128), jnp.float32)

        def loss(fn):
            def f(x_, w_):
                return jnp.sum(fn(x_, w_) ** 2)
            return jax.value_and_grad(f, (0, 1))

        ref_v, ref_g = loss(lambda a, b: rms_norm_ref(a, b, 1e-6))(x, w)
        flags.set_flags({"FLAGS_pallas_interpret": True})
        try:
            got_v, got_g = loss(lambda a, b: rms_norm_train_sharded(
                a, b, 1e-6, mesh, spec))(x, w)
        finally:
            flags.set_flags({"FLAGS_pallas_interpret": False})
        np.testing.assert_allclose(float(got_v), float(ref_v), rtol=1e-5)
        for a, b in zip(got_g, ref_g):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


class TestNormDoubleGrad:
    """ADVICE r4 item 2: double-grad/HVPs through the fused norm
    backwards must not hit a bare pallas_call — the second-order rule
    rides the jnp twin. Verified in interpret mode vs the pure-ref HVP."""

    def test_rms_hvp_matches_ref(self):
        import numpy as np
        import jax
        import jax.numpy as jnp
        from paddle_tpu.core import flags
        from paddle_tpu.kernels.rms_norm import rms_norm_ref, rms_norm_train
        x = jnp.asarray(np.random.RandomState(0).randn(8, 128), jnp.float32)
        w = jnp.asarray(np.random.RandomState(1).rand(128), jnp.float32)
        v = jnp.asarray(np.random.RandomState(2).randn(8, 128), jnp.float32)

        def loss(fn, x_):
            return jnp.sum(fn(x_, w) ** 2)

        def hvp_of(fn):
            # reverse-over-reverse (the tape's double-grad formulation)
            g = jax.grad(lambda a: loss(fn, a))
            return jax.grad(lambda a: jnp.vdot(g(a), v))(x)

        flags.set_flags({"FLAGS_pallas_interpret": True})
        try:
            hvp = hvp_of(lambda p, q: rms_norm_train(p, q, 1e-6, True))
        finally:
            flags.set_flags({"FLAGS_pallas_interpret": False})
        ref = hvp_of(lambda p, q: rms_norm_ref(p, q, 1e-6))
        np.testing.assert_allclose(np.asarray(hvp), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_ln_hvp_matches_ref(self):
        import numpy as np
        import jax
        import jax.numpy as jnp
        from paddle_tpu.core import flags
        from paddle_tpu.kernels.layer_norm import (layer_norm_ref,
                                                   layer_norm_train)
        x = jnp.asarray(np.random.RandomState(3).randn(8, 128), jnp.float32)
        w = jnp.asarray(np.random.RandomState(4).rand(128), jnp.float32)
        b = jnp.asarray(np.random.RandomState(5).randn(128), jnp.float32)
        v = jnp.asarray(np.random.RandomState(6).randn(8, 128), jnp.float32)

        def hvp_of(fn):
            g = jax.grad(lambda a: jnp.sum(fn(a, w, b) ** 2))
            return jax.grad(lambda a: jnp.vdot(g(a), v))(x)

        flags.set_flags({"FLAGS_pallas_interpret": True})
        try:
            hvp = hvp_of(layer_norm_train)
        finally:
            flags.set_flags({"FLAGS_pallas_interpret": False})
        ref = hvp_of(layer_norm_ref)
        np.testing.assert_allclose(np.asarray(hvp), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
