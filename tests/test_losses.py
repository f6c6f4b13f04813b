"""Unit tests for the paper's loss functions (Eq. 1/3/5) against hand
calculations and reference formulations."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import losses


def test_cross_entropy_matches_manual():
    logits = jnp.array([[2.0, 0.0, -1.0], [0.0, 0.0, 0.0]])
    labels = jnp.array([0, 2])
    got = float(losses.cross_entropy(logits, labels))
    p0 = np.exp(2.0) / (np.exp(2.0) + 1 + np.exp(-1.0))
    want = (-np.log(p0) - np.log(1 / 3)) / 2
    assert abs(got - want) < 1e-5


def test_cross_entropy_mask_excludes_samples():
    logits = jnp.array([[5.0, 0.0], [0.0, 5.0]])
    labels = jnp.array([1, 1])  # first sample wrong, second right
    m_all = float(losses.cross_entropy(logits, labels))
    m_second = float(losses.cross_entropy(logits, labels,
                                          mask=jnp.array([False, True])))
    assert m_second < m_all
    # fully-masked -> 0, not NaN
    z = float(losses.cross_entropy(logits, labels,
                                   mask=jnp.zeros(2, bool)))
    assert z == 0.0


def test_pseudo_labels_threshold():
    logits = jnp.array([[10.0, 0.0], [0.1, 0.0]])
    labels, ok, conf = losses.pseudo_labels(logits, tau=0.95)
    assert labels.tolist() == [0, 0]
    assert ok.tolist() == [True, False]


def test_consistency_loss_eq1():
    """Eq. (1): only above-threshold samples contribute."""
    t_logits = jnp.array([[10.0, 0.0], [0.3, 0.0]])
    s_logits = jnp.array([[0.0, 3.0], [0.0, 3.0]])
    loss, mask_rate = losses.consistency_loss(s_logits, t_logits, tau=0.95)
    # only sample 0 participates: CE(s_logits[0], label 0)
    want = -jax.nn.log_softmax(s_logits[0])[0]
    assert abs(float(loss) - float(want)) < 1e-5
    assert abs(float(mask_rate) - 0.5) < 1e-6


def _manual_contrastive(z, ref, pos_mask, valid, kappa):
    z = np.asarray(z, np.float64)
    ref = np.asarray(ref, np.float64)
    logits = z @ ref.T / kappa
    logits[:, ~valid] = -np.inf
    out, cnt = 0.0, 0
    for j in range(z.shape[0]):
        pos = np.where(pos_mask[j] & valid)[0]
        if len(pos) == 0:
            continue
        lse = np.log(np.sum(np.exp(logits[j][np.isfinite(logits[j])])))
        out += -np.mean(logits[j, pos] - lse)
        cnt += 1
    return out / max(cnt, 1)


def test_clustering_loss_eq5_matches_manual(rng):
    b, q, d, m = 6, 12, 4, 3
    z = rng.randn(b, d).astype(np.float32)
    qz = rng.randn(q, d).astype(np.float32)
    pseudo = rng.randint(0, m, b)
    qlab = rng.randint(0, m, q)
    qconf = rng.rand(q) > 0.4
    qvalid = rng.rand(q) > 0.2
    aok = np.ones(b, bool)
    got = float(losses.clustering_loss(
        jnp.asarray(z), jnp.asarray(pseudo), jnp.asarray(aok),
        jnp.asarray(qz), jnp.asarray(qlab), jnp.asarray(qconf),
        jnp.asarray(qvalid), 0.5))
    pos = (pseudo[:, None] == qlab[None, :]) & qconf[None, :]
    want = _manual_contrastive(z, qz, pos, qvalid, 0.5)
    assert abs(got - want) < 1e-4


def test_clustering_loss_ignores_below_threshold_queue_entries(rng):
    """Positives must have queue confidence; invalid entries never appear
    in the denominator."""
    b, q, d = 4, 8, 3
    z = jnp.asarray(rng.randn(b, d), jnp.float32)
    qz = jnp.asarray(rng.randn(q, d), jnp.float32)
    pseudo = jnp.zeros(b, jnp.int32)
    qlab = jnp.zeros(q, jnp.int32)
    aok = jnp.ones(b, bool)
    valid = jnp.ones(q, bool)
    no_conf = jnp.zeros(q, bool)
    loss = losses.clustering_loss(z, pseudo, aok, qz, qlab, no_conf, valid,
                                  0.1)
    assert float(loss) == 0.0  # no positives anywhere -> zero loss


def test_supervised_contrastive_excludes_self(rng):
    b, d = 5, 4
    z = jnp.asarray(rng.randn(b, d), jnp.float32)
    labels = jnp.asarray([0, 0, 1, 1, 2])
    # empty queue
    qz = jnp.zeros((3, d), jnp.float32)
    qvalid = jnp.zeros(3, bool)
    loss = losses.supervised_contrastive_loss(z, labels, qz,
                                              jnp.zeros(3, jnp.int32),
                                              qvalid, 0.5)
    assert np.isfinite(float(loss))
    # label 2 has no positives -> contributes nothing; perturbing z[4]
    # tangentially must not change the count of contributing anchors
    g = jax.grad(lambda zz: losses.supervised_contrastive_loss(
        zz, labels, qz, jnp.zeros(3, jnp.int32), qvalid, 0.5))(z)
    assert np.isfinite(np.asarray(g)).all()


def test_projection_head_gradient_finite_at_a_zero_vector(rng):
    """A sample whose hidden projection is all zero (ReLU-dead, or zero
    pooled features) projects to z = 0.  The l2 norm's gradient there is
    0/0; left unguarded it turned every projection weight NaN in one step
    (paper-cnn on a TPU v5e, near round 990 of a cnn-default window).
    Nonzero rows keep the plain formula's value and gradient bit for bit."""
    from repro.configs import smoke_config
    from repro.core.split import apply_projection_head, init_projection_head
    cfg = smoke_config("paper-cnn")
    p = init_projection_head(jax.random.PRNGKey(0), cfg)
    feats = jnp.asarray(rng.rand(8, p["w1"].shape[0]), jnp.float32)
    weights = jnp.arange(cfg.semisfl.proj_dim, dtype=jnp.float32)

    def plain(p, f):
        x = jax.nn.relu(f @ p["w1"]) @ p["w2"]
        return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True),
                               1e-6)

    def loss(head, p, f):
        return (head(p, f) * weights).sum()

    ours = lambda p, f: apply_projection_head(p, cfg, f)
    np.testing.assert_array_equal(ours(p, feats), plain(p, feats))
    for a, b in zip(jax.tree.leaves(jax.grad(loss, 1)(ours, p, feats)),
                    jax.tree.leaves(jax.grad(loss, 1)(plain, p, feats))):
        np.testing.assert_array_equal(a, b)

    dead = feats.at[3].set(0.0)
    assert not np.asarray(ours(p, dead)[3]).any()
    grads = jax.grad(loss, 1)(ours, p, dead)
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree.leaves(grads))
    # the plain formula is the fault this guards against
    assert not all(np.isfinite(np.asarray(g)).all()
                   for g in jax.tree.leaves(jax.grad(loss, 1)(plain, p, dead)))
