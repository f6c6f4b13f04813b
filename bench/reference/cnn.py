"""Plain reference of SemiSFL aggregation rounds for the paper's CNN family.

Written from the paper (Section III, Alg. 1) and the configuration file
alone, in straightforward ``jax.numpy`` at float32: no kernels, no scan,
no executor, nothing imported from the program.  It makes its own
weights and its own batches from the seed, by the rules the
configuration's deployment follows:

* weights: He-normal k x k convolutions (``conv_kernel``), 1/sqrt(fan_in)
  dense layers and an MLP projection head, drawn from ``PRNGKey(seed)``
  split three ways (model, projection head, the state's key);
* batches: each loader shuffles its index set with
  ``RandomState(seed)`` and starts a fresh permutation when one runs out;
  the labeled loader is seeded with ``seed``, client ``i``'s with
  ``seed + 1 + 31 i``, and the active clients of each round are drawn
  without replacement from ``RandomState(seed)``;
* augmentation and dropout keys are split from the state's key, step by
  step, in the order the round consumes them.

One round: K_s supervised steps (cross-entropy plus the supervised
contrastive term against the queue, Eq. (3)-(4); SGD with momentum;
teacher EMA; the teacher's features of the batch enqueued with their
labels), the global and teacher bottoms copied to the active clients,
K_u cross-entity steps (teacher pseudo-labels gated by tau; consistency
Eq. (1) plus clustering Eq. (5) against the queue; the top and head
step on the mean gradient, Eq. (7), each client's bottom on its own,
Eq. (8); client teacher bottoms by EMA; pseudo-labelled teacher
features enqueued), then FedAvg of the bottoms and teacher bottoms.

``precision`` is the matmul and convolution precision: ``"highest"`` is
float32 as the configuration states; ``"high"`` is the control that has
to fail the comparison: every product split into bfloat16 high and low
parts and summed from three single passes (hi*hi + hi*lo + lo*hi, as the
TPU computes float32 at ``high``), written out so that it is the same
arithmetic on every backend.  ``fault="half"`` drops
the second half of every batch and takes the means over the rest, a
planted fault whose readings set a limit's upper end.
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30
PRECISIONS = ("highest", "high")
HIGHEST, DEFAULT = jax.lax.Precision.HIGHEST, jax.lax.Precision.DEFAULT


class Queue(NamedTuple):
    z: jax.Array
    label: jax.Array
    conf: jax.Array
    valid: jax.Array
    ptr: jax.Array


class State(NamedTuple):
    params: dict
    teacher: dict
    mu: dict
    queue: Queue
    rng: jax.Array


class Sampler:
    """Shuffled batches over an index set, epoch after epoch."""

    def __init__(self, idx: np.ndarray, batch: int, seed: int):
        self.idx, self.batch = np.asarray(idx), batch
        self.rng = np.random.RandomState(seed)
        self.order, self.cursor = self.rng.permutation(self.idx), 0

    def next(self) -> np.ndarray:
        out, filled = np.empty(self.batch, self.idx.dtype), 0
        while filled < self.batch:
            if self.cursor == len(self.order):
                self.order, self.cursor = self.rng.permutation(self.idx), 0
            m = min(self.batch - filled, len(self.order) - self.cursor)
            out[filled:filled + m] = self.order[self.cursor:self.cursor + m]
            self.cursor += m
            filled += m
        return out


# ---------------------------------------------------------------- model

def pooled(cfg: dict) -> list:
    """Per conv, whether a 2x2 max-pool follows it (``pool_after`` counts
    convs from 1)."""
    return [i + 1 in cfg["pool_after"]
            for i in range(len(cfg["cnn_channels"]))]


def init_params(cfg: dict, key) -> dict:
    ch, fc, k = cfg["cnn_channels"], cfg["cnn_fc"], cfg["conv_kernel"]
    n, split = len(ch), cfg["split_layer"]
    keys = jax.random.split(key, n + len(fc) + 2)
    dense = lambda k, i, o: (jax.random.normal(k, (i, o), jnp.float32)
                             * (1.0 / math.sqrt(i)))
    convs, cin = [], 3
    for i, cout in enumerate(ch):
        w = jax.random.normal(keys[i], (k, k, cin, cout), jnp.float32)
        convs.append({"w": w * (2.0 / (k * k * cin)) ** 0.5,
                      "b": jnp.zeros((cout,), jnp.float32)})
        cin = cout
    hw = cfg["image_size"] // 2 ** sum(pooled(cfg))
    feat, fcs = hw * hw * ch[-1], []
    for j, width in enumerate(fc):
        fcs.append({"w": dense(keys[n + j], feat, width),
                    "b": jnp.zeros((width,), jnp.float32)})
        feat = width
    cls = {"w": dense(keys[-1], feat, cfg["num_classes"]),
           "b": jnp.zeros((cfg["num_classes"],), jnp.float32)}
    return {"bottom": {"convs": convs[:split]},
            "top": {"convs": convs[split:], "fcs": fcs, "cls": cls}}


def init_head(cfg: dict, key) -> dict:
    k1, k2 = jax.random.split(key)
    d_in = cfg["cnn_channels"][cfg["split_layer"] - 1]
    h, d = cfg["proj_hidden"], cfg["proj_dim"]
    return {"w1": jax.random.normal(k1, (d_in, h), jnp.float32)
            * (1.0 / math.sqrt(d_in)),
            "w2": jax.random.normal(k2, (h, d), jnp.float32)
            * (1.0 / math.sqrt(h))}


def _bf16_parts(a):
    """float32 ``a`` as high and low parts whose values bfloat16 holds
    exactly, so a product of two parts is exact in float32."""
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (a - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _sum3(f, a, b):
    (ah, al), (bh, bl) = _bf16_parts(a), _bf16_parts(b)
    return f(ah, bh) + (f(ah, bl) + f(al, bh))


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def three_pass(op, a, b):
    """Bilinear ``op(a, b)`` from three single bfloat16 passes with float32
    sums; its gradients are made the same way."""
    return _sum3(op, a, b)


def _three_pass_fwd(op, a, b):
    return _sum3(op, a, b), (a, b)


def _three_pass_bwd(op, res, g):
    a, b = res
    da = lambda g_, b_: jax.vjp(lambda x: op(x, b_), a)[1](g_)[0]
    db = lambda a_, g_: jax.vjp(lambda y: op(a_, y), b)[1](g_)[0]
    return _sum3(da, g, b), _sum3(db, a, g)


three_pass.defvjp(_three_pass_fwd, _three_pass_bwd)


def _matmul(a, b, **kw):
    return jax.lax.dot_general(a, b, (((a.ndim - 1,), (0,)), ((), ())), **kw)


def _conv(x, w, **kw):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        **kw)


class Model:
    def __init__(self, cfg: dict, precision: str):
        if precision not in PRECISIONS:
            raise ValueError(f"precision is one of {PRECISIONS}, "
                             f"not {precision!r}")
        self.cfg = cfg
        self.pool = pooled(cfg)
        if precision == "high":
            # a single pass over parts that bfloat16 holds exactly is exact
            self.mm = partial(three_pass, partial(_matmul, precision=DEFAULT))
            self.conv = partial(three_pass, partial(_conv, precision=DEFAULT))
        else:
            self.mm = partial(_matmul, precision=HIGHEST)
            self.conv = partial(_conv, precision=HIGHEST)

    def convs(self, layers, x, first: int):
        for i, p in enumerate(layers):
            x = jax.nn.relu(self.conv(x, p["w"]) + p["b"])
            if self.pool[first + i]:
                x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                          (1, 2, 2, 1), (1, 2, 2, 1),
                                          "VALID")
        return x

    def bottom(self, p, x):
        return self.convs(p["convs"], x, 0)

    def top(self, p, feats, drop_keys=None):
        x = self.convs(p["convs"], feats, self.cfg["split_layer"])
        x = x.reshape(x.shape[0], -1)
        rate = self.cfg["cnn_dropout"]
        for layer, q in enumerate(p["fcs"]):
            x = jax.nn.relu(self.mm(x, q["w"]) + q["b"])
            if drop_keys is not None and rate > 0:
                keep = jax.vmap(lambda k, row, l=layer: jax.random.bernoulli(
                    jax.random.fold_in(k, l), 1.0 - rate, row.shape))(
                        drop_keys, x)
                x = jnp.where(keep, x / (1.0 - rate), 0.0)
        return self.mm(x, p["cls"]["w"]) + p["cls"]["b"]

    def head(self, p, feats):
        x = self.mm(feats.mean(axis=(1, 2)), p["w1"])
        x = self.mm(jax.nn.relu(x), p["w2"])
        return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True),
                               1e-6)

    def forward(self, params, x, drop_keys=None):
        feats = self.bottom(params["bottom"], x)
        return (self.top(params["top"], feats, drop_keys),
                self.head(params["proj"], feats))


# --------------------------------------------------------- augmentation

def weak_augment(key, x, pad: int = 4):
    """Random horizontal flip, then a random crop of the reflect-padded
    image."""
    b, h, w, c = x.shape
    k_flip, k_crop = jax.random.split(key)
    flip = jax.random.bernoulli(k_flip, 0.5, (b, 1, 1, 1))
    x = jnp.where(flip, x[:, :, ::-1, :], x)
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)), mode="reflect")
    k1, k2 = jax.random.split(k_crop)
    dx = jax.random.randint(k1, (b,), 0, 2 * pad + 1)
    dy = jax.random.randint(k2, (b,), 0, 2 * pad + 1)
    return jax.vmap(lambda img, ox, oy: jax.lax.dynamic_slice(
        img, (ox, oy, 0), (h, w, c)))(xp, dx, dy)


def strong_augment(key, x, magnitude: float = 0.5, cutout: float = 0.25):
    """Weak augmentation, then two ops drawn from {brightness, contrast}
    at the given magnitude, then a grey cutout square."""
    b, h, w, _ = x.shape
    keys = jax.random.split(key, 5)
    x = weak_augment(keys[0], x)
    for i in (1, 2):
        k_pick, k_op = jax.random.split(keys[i])
        pick = jax.random.randint(k_pick, (), 0, 2)
        u = jax.random.uniform(k_op, (b, 1, 1, 1)) * 2 - 1
        bright = x + u * magnitude
        mean = x.mean(axis=(1, 2, 3), keepdims=True)
        contrast = (x - mean) * (1.0 + u * magnitude) + mean
        x = jnp.where(pick == 0, bright, contrast)
    side = max(1, int(h * cutout))
    k1, k2 = jax.random.split(keys[4])
    cy = jax.random.randint(k1, (b,), 0, h - side + 1)[:, None, None]
    cx = jax.random.randint(k2, (b,), 0, w - side + 1)[:, None, None]
    ys, xs = jnp.arange(h)[None, :, None], jnp.arange(w)[None, None, :]
    box = (ys >= cy) & (ys < cy + side) & (xs >= cx) & (xs < cx + side)
    return jnp.clip(jnp.where(box[..., None], 0.5, x), 0.0, 1.0)


# --------------------------------------------------------------- losses

def cross_entropy(logits, labels, mask=None):
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    if mask is None:
        return -ll.mean()
    m = mask.astype(jnp.float32)
    return -(ll * m).sum() / jnp.maximum(m.sum(), 1.0)


def contrastive(mm, z, ref, pos, valid, temperature):
    """Mean over anchors with a positive of -mean_{p in P} log softmax
    over the valid references; the references carry no gradient."""
    logits = mm(z, jax.lax.stop_gradient(ref).T) / temperature
    logp = jax.nn.log_softmax(jnp.where(valid[None], logits, NEG_INF), -1)
    pos = pos & valid[None]
    n_pos = pos.sum(-1)
    per = -jnp.where(pos, logp, 0.0).sum(-1) / jnp.maximum(n_pos, 1)
    has = n_pos > 0
    return jnp.where(has, per, 0.0).sum() / jnp.maximum(has.sum(), 1)


def enqueue(q: Queue, z, labels, conf) -> Queue:
    qlen = q.z.shape[0]
    slots = (q.ptr + jnp.arange(z.shape[0])) % qlen
    return Queue(q.z.at[slots].set(z), q.label.at[slots].set(labels),
                 q.conf.at[slots].set(conf), q.valid.at[slots].set(True),
                 (q.ptr + z.shape[0]) % qlen)


ema = lambda t, s, g: jax.tree.map(lambda a, b: g * a + (1.0 - g) * b, t, s)


# ---------------------------------------------------------------- steps

class Reference:
    """K_s supervised and K_u cross-entity steps per round, jitted one
    step at a time."""

    def __init__(self, cfg: dict, *, precision: str = "highest",
                 fault: str | None = None):
        if cfg["observation_period"] * (cfg["adaptation_window"] + 1) <= 3:
            raise ValueError("K_s could adapt within the three set-up "
                             "rounds; the reference keeps K_s fixed")
        self.cfg, self.fault = cfg, fault
        self.m = Model(cfg, precision)
        self.drop = cfg["cnn_dropout"] > 0
        self.sup = jax.jit(self._supervised)
        self.semi = jax.jit(self._cross_entity)
        self.fedavg = jax.jit(lambda t: jax.tree.map(lambda a: a.mean(0), t))

    def init(self, seed: int, teacher_scale: float) -> State:
        k_model, k_head, k_state = jax.random.split(jax.random.PRNGKey(seed),
                                                    3)
        params = dict(init_params(self.cfg, k_model),
                      proj=init_head(self.cfg, k_head))
        teacher = jax.tree.map(jnp.copy, params)
        teacher["top"]["cls"]["w"] = teacher["top"]["cls"]["w"] * \
            teacher_scale
        q, d = self.cfg["queue_len"], self.cfg["proj_dim"]
        queue = Queue(jnp.zeros((q, d), jnp.float32),
                      jnp.zeros((q,), jnp.int32), jnp.zeros((q,), bool),
                      jnp.zeros((q,), bool), jnp.zeros((), jnp.int32))
        return State(params, teacher, jax.tree.map(jnp.zeros_like, params),
                     queue, k_state)

    def _supervised(self, s: State, x, y):
        c, m = self.cfg, self.m
        if self.fault == "half":
            x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
        if self.drop:
            rng, k_aug, k_drop = jax.random.split(s.rng, 3)
            drop_keys = jax.random.split(k_drop, x.shape[0])
        else:
            rng, k_aug = jax.random.split(s.rng)
            drop_keys = None
        xa = weak_augment(k_aug, x)
        q, b = s.queue, x.shape[0]

        def loss_fn(params):
            logits, z = m.forward(params, xa, drop_keys)
            ref = jnp.concatenate([z, q.z])
            ref_y = jnp.concatenate([y, q.label])
            valid = jnp.concatenate([jnp.ones((b,), bool), q.valid & q.conf])
            pos = (y[:, None] == ref_y[None]) & ~jnp.eye(b, b + len(q.label),
                                                          dtype=bool)
            return cross_entropy(logits, y) + contrastive(
                m.mm, z, ref, pos, valid, c["temperature"])

        loss, g = jax.value_and_grad(loss_fn)(s.params)
        mu = jax.tree.map(lambda a, b: c["momentum"] * a + b, s.mu, g)
        params = jax.tree.map(lambda p, u: p - c["lr"] * u, s.params, mu)
        teacher = ema(s.teacher, params, c["ema_decay"])
        _, tz = m.forward(teacher, xa)
        queue = enqueue(q, tz, y, jnp.ones((b,), bool))
        return State(params, teacher, mu, queue, rng), loss

    def _cross_entity(self, carry, xu):
        c, m = self.cfg, self.m
        bottoms, t_bottoms, top, proj, teacher, q, rng = carry
        if self.fault == "half":
            xu = xu[:, : xu.shape[1] // 2]
        n, b = xu.shape[:2]
        if self.drop:
            rng, kw, ks, kd = jax.random.split(rng, 4)
            drop_keys = jax.random.split(kd, n * b)
        else:
            rng, kw, ks = jax.random.split(rng, 3)
            drop_keys = None
        xw = jax.vmap(weak_augment)(jax.random.split(kw, n), xu)
        xs = jax.vmap(strong_augment)(jax.random.split(ks, n), xu)
        flat = lambda f: f.reshape((n * b,) + f.shape[2:])

        t_feats = flat(jax.vmap(m.bottom)(t_bottoms, xw))
        probs = jax.nn.softmax(m.top(teacher["top"], t_feats), axis=-1)
        pseudo, conf = probs.argmax(-1), probs.max(-1) > c["tau"]
        tz = m.head(teacher["proj"], t_feats)

        def loss_fn(bottoms, top, proj):
            feats = flat(jax.vmap(m.bottom)(bottoms, xs))
            h = cross_entropy(m.top(top, feats, drop_keys), pseudo, conf)
            pos = ((pseudo[:, None] == q.label[None]) & q.conf[None]
                   & conf[:, None])
            return h + contrastive(m.mm, m.head(proj, feats), q.z, pos,
                                   q.valid, c["temperature"])

        loss, (g_b, g_t, g_p) = jax.value_and_grad(loss_fn, (0, 1, 2))(
            bottoms, top, proj)
        step = lambda p, g: jax.tree.map(lambda a, d: a - c["lr"] * d, p, g)
        # each client steps on its own gradient: undo the mean's 1/n
        bottoms = step(bottoms, jax.tree.map(lambda g: g * n, g_b))
        t_bottoms = ema(t_bottoms, bottoms, c["ema_decay"])
        carry = (bottoms, t_bottoms, step(top, g_t), step(proj, g_p),
                 teacher, enqueue(q, tz, pseudo, conf), rng)
        return carry, (loss, 1.0 - conf.mean())

    def rounds(self, state: State, data, seed: int, n_rounds: int, *,
               mix: dict, k_s: int, k_u: int, n_labeled: int, mesh=None):
        """Run ``n_rounds`` rounds.  Returns the state after the first,
        the state after the last, and per round (f_s, f_u, mask_rate).
        With a ``mesh`` the client axis is spread over its first axis, so
        that a cohort larger than one chip holds fits."""
        lab = Sampler(np.arange(n_labeled), mix["labeled_batch"], seed)
        clients = [Sampler(p, mix["client_batch"], seed + 1 + 31 * i)
                   for i, p in enumerate(data.parts)]
        select = np.random.RandomState(seed)
        n_act = min(mix["n_active"], len(clients))
        x_all, y_all = data.train.x, data.train.y
        put_clients = lambda t: t
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            spread = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
            put_clients = lambda t: jax.device_put(t, spread)
            state = jax.device_put(state, NamedSharding(mesh,
                                                        PartitionSpec()))
        first, metrics = None, []
        for _ in range(n_rounds):
            f_s = []
            for _ in range(k_s):
                rows = lab.next()
                state, loss = self.sup(state, jnp.asarray(x_all[rows]),
                                       jnp.asarray(y_all[rows]))
                f_s.append(float(loss))
            active = select.choice(len(clients), size=n_act, replace=False)
            copies = lambda t: put_clients(jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_act,) + a.shape), t))
            carry = (copies(state.params["bottom"]),
                     copies(state.teacher["bottom"]), state.params["top"],
                     state.params["proj"], state.teacher, state.queue,
                     state.rng)
            f_u, masks = [], []
            for _ in range(k_u):
                xu = np.stack([x_all[clients[i].next()] for i in active])
                carry, (loss, mask) = self.semi(carry,
                                                put_clients(jnp.asarray(xu)))
                f_u.append(float(loss))
                masks.append(float(mask))
            bottoms, t_bottoms, top, proj, teacher, queue, rng = carry
            state = State({"bottom": self.fedavg(bottoms), "top": top,
                           "proj": proj},
                          dict(teacher, bottom=self.fedavg(t_bottoms)),
                          state.mu, queue, rng)
            metrics.append((float(np.mean(f_s)), float(np.mean(f_u)),
                            float(np.mean(masks))))
            first = state if first is None else first
        return first, state, metrics
