"""Round-5 distribution completions (reference:
``python/paddle/distribution/`` †): Cauchy/Chi2/Binomial/
ContinuousBernoulli/MultivariateNormal/LKJCholesky, Independent +
TransformedDistribution wrappers, and the Transform bijector family —
all pinned against torch.distributions oracles."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distribution as D

torch = pytest.importorskip("torch")


def _t(a):
    return paddle.to_tensor(np.asarray(a))


class TestNewDistributions:
    def test_cauchy_matches_torch(self):
        c = D.Cauchy(_t(np.float32(1.0)), _t(np.float32(2.0)))
        tc = torch.distributions.Cauchy(1.0, 2.0)
        v = np.linspace(-5, 5, 7, dtype=np.float32)
        np.testing.assert_allclose(c.log_prob(_t(v)).numpy(),
                                   tc.log_prob(torch.tensor(v)).numpy(),
                                   rtol=1e-5)
        np.testing.assert_allclose(float(c.entropy()), float(tc.entropy()),
                                   rtol=1e-5)
        np.testing.assert_allclose(c.cdf(_t(v)).numpy(),
                                   tc.cdf(torch.tensor(v)).numpy(),
                                   rtol=1e-5)

    def test_chi2_matches_torch(self):
        x2 = D.Chi2(_t(np.float32(3.0)))
        v = np.asarray([0.5, 1.0, 4.0], np.float32)
        np.testing.assert_allclose(
            x2.log_prob(_t(v)).numpy(),
            torch.distributions.Chi2(3.0).log_prob(torch.tensor(v)).numpy(),
            rtol=1e-4)

    def test_binomial_matches_torch(self):
        paddle.seed(1)
        b = D.Binomial(_t(np.float32(10)), _t(np.float32(0.3)))
        k = np.asarray([0.0, 3.0, 10.0], np.float32)
        np.testing.assert_allclose(
            b.log_prob(_t(k)).numpy(),
            torch.distributions.Binomial(10, 0.3).log_prob(
                torch.tensor(k)).numpy(), rtol=1e-5)
        s = b.sample((4000,)).numpy()
        assert abs(s.mean() - 3.0) < 0.15

    def test_continuous_bernoulli_matches_torch(self):
        x = np.asarray([0.1, 0.5, 0.9], np.float32)
        for p in (0.3, 0.5):  # incl. the Taylor-limit region
            cb = D.ContinuousBernoulli(_t(np.float32(p)))
            tcb = torch.distributions.ContinuousBernoulli(p)
            np.testing.assert_allclose(
                cb.log_prob(_t(x)).numpy(),
                tcb.log_prob(torch.tensor(x)).numpy(), rtol=1e-3)
        np.testing.assert_allclose(
            float(D.ContinuousBernoulli(_t(np.float32(0.3))).mean),
            float(torch.distributions.ContinuousBernoulli(0.3).mean),
            rtol=1e-4)

    def test_multivariate_normal_matches_torch(self):
        paddle.seed(2)
        rng = np.random.RandomState(0)
        A = rng.randn(3, 3).astype(np.float32)
        cov = (A @ A.T + 3 * np.eye(3)).astype(np.float32)
        loc = rng.randn(3).astype(np.float32)
        mv = D.MultivariateNormal(_t(loc), covariance_matrix=_t(cov))
        tmv = torch.distributions.MultivariateNormal(torch.tensor(loc),
                                                     torch.tensor(cov))
        pt = rng.randn(5, 3).astype(np.float32)
        np.testing.assert_allclose(mv.log_prob(_t(pt)).numpy(),
                                   tmv.log_prob(torch.tensor(pt)).numpy(),
                                   rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(float(mv.entropy()), float(tmv.entropy()),
                                   rtol=1e-4)
        s = mv.sample((8000,)).numpy()
        np.testing.assert_allclose(s.mean(0), loc, atol=0.15)
        np.testing.assert_allclose(np.cov(s.T), cov, atol=0.4)

    def test_lkj_cholesky(self):
        paddle.seed(3)
        lkj = D.LKJCholesky(3, _t(np.float32(1.5)))
        L = lkj.sample((500,)).numpy()
        corr = L @ np.swapaxes(L, -1, -2)
        np.testing.assert_allclose(
            np.diagonal(corr, axis1=-2, axis2=-1), 1.0, atol=1e-4)
        tlkj = torch.distributions.LKJCholesky(3, 1.5)
        L1 = np.asarray(tlkj.sample((1,))[0], np.float32)
        np.testing.assert_allclose(float(lkj.log_prob(_t(L1))),
                                   float(tlkj.log_prob(torch.tensor(L1))),
                                   rtol=1e-4)


class TestNewKLs:
    def test_cauchy_and_mvn_kl_match_torch(self):
        rng = np.random.RandomState(0)
        p = D.Cauchy(_t(np.float32(0.0)), _t(np.float32(1.0)))
        q = D.Cauchy(_t(np.float32(2.0)), _t(np.float32(3.0)))
        np.testing.assert_allclose(
            float(D.kl_divergence(p, q)),
            float(torch.distributions.kl_divergence(
                torch.distributions.Cauchy(0.0, 1.0),
                torch.distributions.Cauchy(2.0, 3.0))), rtol=1e-5)
        A = rng.randn(3, 3).astype(np.float32)
        c1 = (A @ A.T + 3 * np.eye(3)).astype(np.float32)
        B = rng.randn(3, 3).astype(np.float32)
        c2 = (B @ B.T + 3 * np.eye(3)).astype(np.float32)
        l1 = rng.randn(3).astype(np.float32)
        l2 = rng.randn(3).astype(np.float32)
        got = float(D.kl_divergence(
            D.MultivariateNormal(_t(l1), covariance_matrix=_t(c1)),
            D.MultivariateNormal(_t(l2), covariance_matrix=_t(c2))))
        want = float(torch.distributions.kl_divergence(
            torch.distributions.MultivariateNormal(torch.tensor(l1),
                                                   torch.tensor(c1)),
            torch.distributions.MultivariateNormal(torch.tensor(l2),
                                                   torch.tensor(c2))))
        np.testing.assert_allclose(got, want, rtol=1e-4)

    def test_mvn_kl_batched_posterior_vs_unbatched_prior(self):
        """r5 review: the standard VI shape — batched posterior against
        an unbatched prior — must broadcast, returning a [B] KL."""
        rng = np.random.RandomState(0)
        locs = rng.randn(4, 3).astype(np.float32)
        A = rng.randn(3, 3).astype(np.float32)
        cov = (A @ A.T + 3 * np.eye(3)).astype(np.float32)
        kl = D.kl_divergence(
            D.MultivariateNormal(_t(locs),
                                 covariance_matrix=_t(np.tile(cov,
                                                              (4, 1, 1)))),
            D.MultivariateNormal(_t(np.zeros(3, np.float32)),
                                 covariance_matrix=_t(
                                     np.eye(3, dtype=np.float32)))).numpy()
        assert kl.shape == (4,)
        want = torch.distributions.kl_divergence(
            torch.distributions.MultivariateNormal(
                torch.tensor(locs), torch.tensor(np.tile(cov, (4, 1, 1)))),
            torch.distributions.MultivariateNormal(
                torch.zeros(3), torch.eye(3))).numpy()
        np.testing.assert_allclose(kl, want, rtol=1e-4)


class TestWrappers:
    def test_independent_sums_event_dims(self):
        rng = np.random.RandomState(1)
        base = D.Normal(_t(np.zeros((4, 3), np.float32)),
                        _t(np.ones((4, 3), np.float32)))
        ind = D.Independent(base, 1)
        tind = torch.distributions.Independent(
            torch.distributions.Normal(torch.zeros(4, 3),
                                       torch.ones(4, 3)), 1)
        v = rng.randn(4, 3).astype(np.float32)
        np.testing.assert_allclose(ind.log_prob(_t(v)).numpy(),
                                   tind.log_prob(torch.tensor(v)).numpy(),
                                   rtol=1e-5)
        assert ind.event_shape == [3] and ind.batch_shape == [4]

    def test_transformed_vector_event_base(self):
        """r5 review: an elementwise transform over a vector-event base
        must keep the vector event (log-det sums over event dims)."""
        rng = np.random.RandomState(0)
        cov = np.eye(2, dtype=np.float32) * 0.5
        td = D.TransformedDistribution(
            D.MultivariateNormal(_t(np.zeros(2, np.float32)),
                                 covariance_matrix=_t(cov)),
            [D.ExpTransform()])
        ttd = torch.distributions.TransformedDistribution(
            torch.distributions.MultivariateNormal(torch.zeros(2),
                                                   torch.tensor(cov)),
            [torch.distributions.transforms.ExpTransform()])
        y = np.abs(rng.randn(5, 2).astype(np.float32)) + 0.2
        np.testing.assert_allclose(td.log_prob(_t(y)).numpy(),
                                   ttd.log_prob(torch.tensor(y)).numpy(),
                                   rtol=1e-4, atol=1e-5)
        assert td.event_shape == [2]

    def test_binomial_degenerate_probs_finite(self):
        b = D.Binomial(_t(np.float32(10)), _t(np.float32(1.0)))
        assert np.isfinite(float(b.log_prob(_t(np.float32(10)))))
        b0 = D.Binomial(_t(np.float32(10)), _t(np.float32(0.0)))
        assert np.isfinite(float(b0.log_prob(_t(np.float32(0)))))

    def test_lkj_sampler_marginal_matches_torch(self):
        """r5 review caught a wrong Beta concentration in the onion
        sampler; pin the (1,0) correlation marginal against torch's
        sampler (same construction => same histogram shape)."""
        paddle.seed(5)
        torch.manual_seed(5)    # unseeded, two samplers' bins differ by
        # more than rtol in one run of twenty (the whole run of PR 54)
        L = D.LKJCholesky(3, _t(np.float32(1.0))).sample((4000,)).numpy()
        corr = (L @ np.swapaxes(L, -1, -2))[:, 1, 0]
        hist, _ = np.histogram(corr, bins=4, range=(-1, 1))
        tL = torch.distributions.LKJCholesky(3, 1.0).sample((4000,))
        tcorr = (tL @ tL.transpose(-1, -2))[:, 1, 0].numpy()
        thist, _ = np.histogram(tcorr, bins=4, range=(-1, 1))
        np.testing.assert_allclose(hist, thist, rtol=0.12)

    def test_transformed_normal_exp_is_lognormal(self):
        td = D.TransformedDistribution(
            D.Normal(_t(np.float32(0.0)), _t(np.float32(1.0))),
            [D.ExpTransform()])
        tl = torch.distributions.LogNormal(0.0, 1.0)
        y = np.asarray([0.5, 1.0, 2.0], np.float32)
        np.testing.assert_allclose(td.log_prob(_t(y)).numpy(),
                                   tl.log_prob(torch.tensor(y)).numpy(),
                                   rtol=1e-5)
        paddle.seed(4)
        s = td.sample((4000,)).numpy()
        assert abs(np.log(s).mean()) < 0.1


class TestTransforms:
    @pytest.mark.parametrize("pair", [
        ("exp", lambda: (D.ExpTransform(),
                         torch.distributions.transforms.ExpTransform())),
        ("sigmoid", lambda: (D.SigmoidTransform(),
                             torch.distributions.transforms.SigmoidTransform())),
        ("tanh", lambda: (D.TanhTransform(),
                          torch.distributions.transforms.TanhTransform())),
        ("affine", lambda: (D.AffineTransform(_t(np.float32(1.0)),
                                              _t(np.float32(-2.0))),
                            torch.distributions.transforms.AffineTransform(
                                1.0, -2.0))),
        ("power", lambda: (D.PowerTransform(_t(np.float32(3.0))),
                           torch.distributions.transforms.PowerTransform(3.0))),
    ], ids=lambda p: p[0] if isinstance(p, tuple) else str(p))
    def test_elementwise_transforms_match_torch(self, pair):
        ours, theirs = pair[1]()
        x = np.asarray([0.3, 0.7, 1.3], np.float32)
        np.testing.assert_allclose(ours.forward(_t(x)).numpy(),
                                   theirs(torch.tensor(x)).numpy(),
                                   rtol=1e-5)
        np.testing.assert_allclose(
            ours.forward_log_det_jacobian(_t(x)).numpy(),
            theirs.log_abs_det_jacobian(
                torch.tensor(x), theirs(torch.tensor(x))).numpy(),
            rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(ours.inverse(ours.forward(_t(x))).numpy(),
                                   x, rtol=1e-4, atol=1e-5)

    def test_stick_breaking_matches_torch(self):
        rng = np.random.RandomState(2)
        sb = D.StickBreakingTransform()
        tsb = torch.distributions.transforms.StickBreakingTransform()
        x = rng.randn(2, 4).astype(np.float32)
        np.testing.assert_allclose(sb.forward(_t(x)).numpy(),
                                   tsb(torch.tensor(x)).numpy(),
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(
            sb.forward_log_det_jacobian(_t(x)).numpy(),
            tsb.log_abs_det_jacobian(torch.tensor(x),
                                     tsb(torch.tensor(x))).numpy(),
            rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(sb.inverse(sb.forward(_t(x))).numpy(), x,
                                   rtol=1e-3, atol=1e-4)
        assert sb.forward_shape((2, 4)) == (2, 5)
        assert sb.inverse_shape((2, 5)) == (2, 4)

    def test_chain_reshape_stack_and_guards(self):
        ch = D.ChainTransform([D.ExpTransform(),
                               D.AffineTransform(_t(np.float32(0.0)),
                                                 _t(np.float32(2.0)))])
        x = np.asarray([0.1, 0.5], np.float32)
        np.testing.assert_allclose(ch.forward(_t(x)).numpy(),
                                   2 * np.exp(x), rtol=1e-5)
        np.testing.assert_allclose(ch.inverse(ch.forward(_t(x))).numpy(), x,
                                   rtol=1e-5)
        rt = D.ReshapeTransform((4,), (2, 2))
        y = rt.forward(_t(np.arange(8, dtype=np.float32).reshape(2, 4)))
        assert y.shape == [2, 2, 2]
        st = D.StackTransform([D.ExpTransform(), D.TanhTransform()], axis=0)
        v = np.stack([x, x])
        out = st.forward(_t(v)).numpy()
        np.testing.assert_allclose(out[0], np.exp(x), rtol=1e-5)
        np.testing.assert_allclose(out[1], np.tanh(x), rtol=1e-5)
        with pytest.raises(NotImplementedError):
            D.AbsTransform().forward_log_det_jacobian(_t(x))
        with pytest.raises(NotImplementedError):
            D.SoftmaxTransform().forward_log_det_jacobian(_t(x))
