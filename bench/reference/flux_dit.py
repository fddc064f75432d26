"""Plain float32 reference of one RL step on the flux_dit velocity field.

Written from the published description of each piece and from the
configuration file alone; it imports nothing of the system under test and
takes nothing the system made.  Weights, conditions and reward towers are
made here again from the seed, by the same recipe (distribution, scale,
order of PRNG keys) that the configuration's initialisation uses, so that
both sides start from the same numbers.

* Conditions: the frozen word-hash text-encoder stand-in (``assumed`` in the
  configuration file): sha1 word ids -> embedding -> tanh layers -> output
  projection.
* Velocity: ``v(x_t, t, c)`` of a DiT with adaLN-zero blocks -- RMSNorm,
  qk-RMSNorm, rotary positions over [condition; latent] tokens, full
  bidirectional softmax attention, gated SiLU MLP -- condition prefix
  projected in, timestep sinusoid -> 2-layer SiLU MLP -> per-block
  modulation.
* Rollout: rectified flow from t=1-1e-4 down to 1e-4 in ``num_steps``
  uniform steps; Flow-SDE transitions (sigma = eta*sqrt(t/(1-t)), t clipped
  to 0.96) for Flow-GRPO, Euler ODE steps for AWM.
* Rewards: PickScore-shaped MLP over pooled latent and condition, and the
  text-render cosine similarity; weighted sum, then normalised within each
  group (population std, +1e-6).
* Losses: Flow-GRPO's PPO-clip objective over every SDE step, and AWM's
  advantage-weighted velocity matching (advantages clipped to +-3), each
  averaged over the batch; gradients by autodiff.
* AdamW with global-norm clipping; parameters kept in the configuration's
  storage dtype (bfloat16) between steps, all arithmetic in float32.

Every matrix product runs at ``precision=highest``.  ``quant`` replaces that
with operands rounded to a narrower float type (per-tensor scaled): that is
the control, the same reference computed below the configuration's
precision.

The unused token embedding and LM head of the system's backbone carry no
gradient and stay unchanged under AdamW; the reference leaves them out.
"""
from __future__ import annotations

import functools
import hashlib
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
T_EPS = 1e-4
LOG2PI = math.log(2.0 * math.pi)


# ------------------------------------------------------------------ weights
def leaf_table(cfg: Dict) -> List[Tuple[str, Tuple[int, ...], str, bool]]:
    """(path, shape, init, used) of every parameter leaf in the order the
    initialisation draws their PRNG keys (paths sorted)."""
    a = cfg["arch"]
    L, d, H, K = a["n_layers"], a["d_model"], a["n_heads"], a["n_kv_heads"]
    hd, f, V = a["head_dim"], a["d_ff"], a["vocab_size"]
    ld, cd = cfg["latent_dim"], cfg["encoder"]["cond_dim"]
    rows = [
        ("backbone/blocks/ada", (L, d, 6 * d), "zeros", True),
        ("backbone/blocks/attn/k_norm", (L, hd), "ones", True),
        ("backbone/blocks/attn/q_norm", (L, hd), "ones", True),
        ("backbone/blocks/attn/wk", (L, d, K, hd), "normal", True),
        ("backbone/blocks/attn/wo", (L, H, hd, d), "normal", True),
        ("backbone/blocks/attn/wq", (L, d, H, hd), "normal", True),
        ("backbone/blocks/attn/wv", (L, d, K, hd), "normal", True),
        ("backbone/blocks/ffn/w_down", (L, f, d), "normal", True),
        ("backbone/blocks/ffn/w_gate", (L, d, f), "normal", True),
        ("backbone/blocks/ffn/w_up", (L, d, f), "normal", True),
        ("backbone/blocks/ln1", (L, d), "ones", True),
        ("backbone/blocks/ln2", (L, d), "ones", True),
        ("backbone/embed", (V, d), "small", False),
        ("backbone/final_norm", (d,), "ones", True),
        ("backbone/lm_head", (d, V), "normal", False),
        ("cond_proj", (cd, d), "normal", True),
        ("latent_in", (ld, d), "normal", True),
        ("latent_out", (d, ld), "small", True),
        ("time_w1", (d, d), "normal", True),
        ("time_w2", (d, d), "normal", True),
    ]
    return rows


def round_to(dtype, x):
    """``x`` rounded to the nearest value of ``dtype``, kept in float32.
    ``reduce_precision`` and not a cast there and back, which the TPU
    compiler may drop as excess precision."""
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


def _init_leaf(key, shape, init, dtype):
    if init == "zeros":
        return jnp.zeros(shape, F32)
    if init == "ones":
        return jnp.ones(shape, F32)
    if init == "small":
        scale = 0.02
    else:                       # fan-in: the second-to-last axis
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        scale = 1.0 / np.sqrt(max(fan_in, 1))
    return round_to(dtype, jax.random.normal(key, shape, F32) * scale)


def init_params(cfg: Dict, seed: int) -> Dict[str, jax.Array]:
    """The used leaves, bfloat16 values held in float32, in one jitted call.
    The trainer's key is PRNGKey(seed); its first half seeds the weights."""
    table = leaf_table(cfg)
    dtype = jnp.dtype(cfg["param_dtype"])

    @jax.jit
    def make(key):
        k_p, _ = jax.random.split(key)
        keys = jax.random.split(k_p, len(table))
        return {path: _init_leaf(k, shape, init, dtype)
                for k, (path, shape, init, used) in zip(keys, table) if used}

    return make(jax.random.PRNGKey(seed))


# ------------------------------------------------------------- conditions
def encode_prompts(cfg: Dict, prompts: Sequence[str]) -> jax.Array:
    """(P, cond_len, cond_dim) condition embeddings of the frozen
    word-hash encoder stand-in."""
    e = cfg["encoder"]
    L, V = e["cond_len"], e["vocab"]
    ids = []
    for p in prompts:
        words = p.lower().split() + ["<pad>"] * L
        ids.append([int(hashlib.sha1(w.encode()).hexdigest()[:8], 16) % V
                    for w in words[:L]])
    ids = jnp.asarray(np.asarray(ids, np.int32))

    @jax.jit
    def enc(ids):
        keys = jax.random.split(jax.random.PRNGKey(e["seed"]), e["depth"] + 2)
        hid = e["hidden"]
        h = jnp.take(jax.random.normal(keys[0], (V, hid), F32) * 0.02, ids,
                     axis=0)
        for k in keys[1:-1]:
            w = jax.random.normal(k, (hid, hid), F32) / np.sqrt(hid)
            h = jnp.tanh(jnp.matmul(h, w, precision="highest"))
        w_out = jax.random.normal(keys[-1], (hid, e["cond_dim"]), F32) \
            / np.sqrt(hid)
        return jnp.matmul(h, w_out, precision="highest")

    return enc(ids)


# ---------------------------------------------------------------- velocity
class Numerics:
    """Matrix products at ``highest`` precision, or -- for the control --
    with both operands of the forward pass first rounded to ``quant``
    (e.g. float8_e4m3fn), each scaled by its largest magnitude as fp8
    matrix products are."""

    def __init__(self, quant: str = ""):
        self.quant = jnp.dtype(quant) if quant else None
        if self.quant is not None:
            self.q = jax.custom_vjp(self._round)
            # the backward pass stays float32: the rounding passes the
            # cotangent through unchanged
            self.q.defvjp(lambda x: (self._round(x), None),
                          lambda _, g: (g,))

    def _round(self, x):
        fi = jnp.finfo(self.quant)
        # largest finite value of the IEEE-style format reduce_precision
        # rounds to (e4m3: 240)
        top = 2.0 ** (2 ** (fi.nexp - 1) - 1) * (2.0 - 2.0 ** -fi.nmant)
        s = jnp.max(jnp.abs(x)) / top
        s = jnp.where(s > 0, s, 1.0)
        return round_to(self.quant, x / s) * s

    def q(self, x):
        return x

    def ein(self, spec, a, b):
        return jnp.einsum(spec, self.q(a), self.q(b), precision="highest")


def rmsnorm(w, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: (B, S, H, D); rotate the two halves by position * frequency."""
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def timestep_features(t, dim, max_period=1e4):
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period) * jnp.arange(half, dtype=F32)
                    / half)
    args = t[:, None] * freqs[None, :] * 1000.0
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], -1)


def velocity(cfg: Dict, num: Numerics, p, x_t, t, cond, remat=False):
    """x_t: (B, Lt, ld); t: (B,); cond: (B, Lc, cond_dim) -> v (B, Lt, ld).
    ``remat`` recomputes each block in the backward pass (memory only)."""
    a = cfg["arch"]
    eps_n, theta, hd = a["norm_eps"], a["rope_theta"], a["head_dim"]
    Lt = x_t.shape[1]
    h_lat = num.ein("bld,de->ble", x_t, p["latent_in"])
    h_cond = num.ein("blc,cd->bld", cond, p["cond_proj"])
    t_emb = num.ein("bd,de->be", jax.nn.silu(num.ein(
        "bd,de->be", timestep_features(t, a["d_model"]), p["time_w1"])),
        p["time_w2"])
    x = jnp.concatenate([h_cond, h_lat], axis=1)
    blocks = {k.split("backbone/blocks/")[1]: v for k, v in p.items()
              if k.startswith("backbone/blocks/")}

    def block(x, blk):
        mod = num.ein("bd,de->be", t_emb, blk["ada"])
        sh_a, sc_a, g_a, sh_m, sc_m, g_m = jnp.split(mod[:, None], 6, -1)
        h = rmsnorm(blk["ln1"], x, eps_n) * (1 + sc_a) + sh_a
        q = rmsnorm(blk["attn/q_norm"],
                    num.ein("bsd,dhk->bshk", h, blk["attn/wq"]), eps_n)
        k = rmsnorm(blk["attn/k_norm"],
                    num.ein("bsd,dhk->bshk", h, blk["attn/wk"]), eps_n)
        v = num.ein("bsd,dhk->bshk", h, blk["attn/wv"])
        q, k = rope(q, theta), rope(k, theta)
        s = num.ein("bqhk,bshk->bhqs", q, k) / math.sqrt(hd)
        o = num.ein("bhqs,bshk->bqhk", jax.nn.softmax(s, -1), v)
        x = x + g_a * num.ein("bshk,hkd->bsd", o, blk["attn/wo"])
        h = rmsnorm(blk["ln2"], x, eps_n) * (1 + sc_m) + sh_m
        mlp = jax.nn.silu(num.ein("bsd,df->bsf", h, blk["ffn/w_gate"])) \
            * num.ein("bsd,df->bsf", h, blk["ffn/w_up"])
        return x + g_m * num.ein("bsf,fd->bsd", mlp, blk["ffn/w_down"])

    if remat:
        block = jax.checkpoint(block)
    for i in range(a["n_layers"]):
        x = block(x, {k: v[i] for k, v in blocks.items()})
    x = rmsnorm(p["backbone/final_norm"], x, eps_n)
    return num.ein("bld,dk->blk", x[:, -Lt:], p["latent_out"])


# ----------------------------------------------------------------- dynamics
def timesteps(T):
    return jnp.linspace(1.0 - T_EPS, T_EPS, T + 1, dtype=F32)


def sde_coefs(eta, t, t_next):
    """(drift coefficient sigma^2/2t, noise std sigma*sqrt(dt)) of Flow-SDE."""
    tc = jnp.clip(t, T_EPS, 0.96)
    sigma = eta * jnp.sqrt(tc / (1.0 - tc))
    return sigma ** 2 / (2.0 * t), sigma * jnp.sqrt(t - t_next)


def sde_mean(eta, v, x, t, t_next):
    coef, _ = sde_coefs(eta, t, t_next)
    return x - (v + coef * (x + (1.0 - t) * v)) * (t - t_next)


def rollout(cfg, num, p, cond_g, key, sde: bool, chunk: int = 4):
    """Trajectories xs (T+1, B, Lt, ld) of the rollout.  Noise is drawn for
    the whole batch from the step key, as the configuration's sampler does;
    the velocity runs ``chunk`` samples at a time to bound memory."""
    T, eta = cfg["num_steps"], cfg["eta"]
    B = cond_g.shape[0]
    ts = timesteps(T)
    k_init, k_steps = jax.random.split(key)
    x = jax.random.normal(k_init, (B, cfg["latent_tokens"], cfg["latent_dim"]),
                          F32)
    step_keys = jax.random.split(k_steps, T)

    def vel(x, t):
        xs = x.reshape((B // chunk, chunk) + x.shape[1:])
        cs = cond_g.reshape((B // chunk, chunk) + cond_g.shape[1:])
        vs = jax.lax.map(lambda a: velocity(cfg, num, p, a[0],
                                            jnp.full((chunk,), t), a[1]),
                         (xs, cs))
        return vs.reshape(x.shape)

    def body(x, inp):
        t, t_next, k = inp
        v = vel(x, t)
        if sde:
            _, std = sde_coefs(eta, t, t_next)
            x_next = sde_mean(eta, v, x, t, t_next) \
                + std * jax.random.normal(k, x.shape, F32)
        else:
            x_next = x - v * (t - t_next)
        return x_next, x_next

    _, tail = jax.lax.scan(body, x, (ts[:-1], ts[1:], step_keys))
    return jnp.concatenate([x[None], tail], 0)


# ------------------------------------------------------------------ rewards
def rewards(cfg, num, x0, cond_g):
    """Per-sample weighted reward sum (B,)."""
    total = 0.0
    ld, cd = cfg["latent_dim"], cfg["encoder"]["cond_dim"]
    pooled_x, pooled_c = x0.mean(axis=1), cond_g.mean(axis=1)
    for spec in cfg["rewards"]:
        kind, w = spec["reward_type"], spec.get("weight", 1.0)
        if kind == "pickscore":
            hid, d_in = 256, ld + cd
            k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
            w1 = jax.random.normal(k1, (d_in, hid), F32) / jnp.sqrt(d_in)
            w2 = jax.random.normal(k2, (hid, hid), F32) / jnp.sqrt(hid)
            w3 = jax.random.normal(k3, (hid, 1), F32) / jnp.sqrt(hid)
            h = jnp.concatenate([pooled_x, pooled_c], -1)
            h = jnp.tanh(num.ein("bi,ij->bj", h, w1))
            h = jnp.tanh(num.ein("bi,ij->bj", h, w2))
            r = num.ein("bi,ij->bj", h, w3)[:, 0]
        elif kind == "text_render":
            proj = jax.random.normal(
                jax.random.PRNGKey(11), (cd, x0.shape[1] * ld), F32) \
                / jnp.sqrt(cd)
            a = x0.reshape(x0.shape[0], -1)
            b = num.ein("bc,cf->bf", pooled_c, proj)
            r = jnp.sum(a * b, -1) / (jnp.linalg.norm(a, axis=-1)
                                      * jnp.linalg.norm(b, axis=-1) + 1e-8)
        else:
            raise ValueError(f"no reference for reward {kind!r}")
        total = total + w * r
    return total


def group_normalize(r, G):
    g = r.reshape(-1, G)
    return ((g - g.mean(1, keepdims=True))
            / (g.std(1, keepdims=True) + 1e-6)).reshape(-1)


# ------------------------------------------------------------------- losses
def loss_terms(cfg, num, xs, cond_g, adv, key):
    """(term, n): the loss is the mean of ``term(p, i)`` over i < n, each
    term a batch-mean over a few samples, so that one term's activations are
    live at a time.  ``term`` returns (loss part, mean |per-sample part|).

    Flow-GRPO: the PPO-clip objective of every SDE step, one sample at a
    time.  The one update per rollout uses the rollout's own parameters, so
    the behaviour log-density equals the current one: the ratio is 1 in
    value and carries the gradient of the current log-density.

    AWM: clip(A, +-3) * |v(x_t, t) - (eps - x0)|^2 (mean over the latent),
    t ~ U(0.02, 0.98) and eps ~ N(0, I) drawn per gradient-accumulation
    chunk from the chunk-index fold of the step key, as the configuration's
    microbatching draws them."""
    T, eta = cfg["num_steps"], cfg["eta"]
    B = cond_g.shape[0]
    if cfg["trainer_type"] == "flow_grpo":
        clip = cfg["clip_range"]
        ts = timesteps(T)

        def term(p, i):
            s, b = i // B, i % B
            x_t = jax.lax.dynamic_index_in_dim(xs, s)
            x_next = jax.lax.dynamic_index_in_dim(xs, s + 1)
            x_t = jax.lax.dynamic_slice_in_dim(x_t[0], b, 1)
            x_next = jax.lax.dynamic_slice_in_dim(x_next[0], b, 1)
            a = jax.lax.dynamic_slice_in_dim(adv, b, 1)
            c = jax.lax.dynamic_slice_in_dim(cond_g, b, 1)
            t, t_next = ts[s], ts[s + 1]
            v = velocity(cfg, num, p, x_t, jnp.full((1,), t), c, remat=True)
            mean = sde_mean(eta, v, x_t, t, t_next)
            _, std = sde_coefs(eta, t, t_next)
            z = (x_next - mean) / std
            logp = jnp.sum(-0.5 * (z * z + LOG2PI) - jnp.log(std), (1, 2))
            ratio = jnp.exp(logp - jax.lax.stop_gradient(logp))
            per = -jnp.minimum(ratio * a,
                               jnp.clip(ratio, 1.0 - clip, 1.0 + clip) * a)
            return per.mean(), jnp.abs(per).mean()

        return term, T * B

    if cfg["trainer_type"] == "awm":
        x0 = xs[-1]
        k = cfg["microbatch"] if cfg["microbatch"] > 1 else 1
        c = B // k

        def term(p, i):
            key_i = jax.random.fold_in(key, i) if k > 1 else key
            k_t, k_eps = jax.random.split(key_i)
            t = jax.random.uniform(k_t, (c,), F32, 0.02, 0.98)
            x0_c = jax.lax.dynamic_slice_in_dim(x0, i * c, c)
            eps = jax.random.normal(k_eps, x0_c.shape, F32)
            x_t = (1.0 - t)[:, None, None] * x0_c + t[:, None, None] * eps
            cond_c = jax.lax.dynamic_slice_in_dim(cond_g, i * c, c)
            v = velocity(cfg, num, p, x_t, t, cond_c, remat=True)
            se = ((v - (eps - x0_c)) ** 2).mean(axis=(1, 2))
            a = jnp.clip(jax.lax.dynamic_slice_in_dim(adv, i * c, c),
                         -3.0, 3.0)
            return (a * se).mean(), jnp.abs(a * se).mean()

        return term, k

    raise ValueError(f"no reference for trainer {cfg['trainer_type']!r}")


def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(x.astype(F32) ** 2)) for k, x in tree.items()}


# --------------------------------------------------------------------- step
def run_steps(cfg: Dict, seed: int, prompt_batches: Sequence[Sequence[str]],
              quant: str = "", keep: float = 1.0) -> Dict:
    """Follow the first ``len(prompt_batches)`` training steps from the seed.

    Returns per step the loss and its scale (the mean absolute per-sample
    term), the per-leaf norm of the clipped gradient the optimizer takes,
    the per-leaf norm of the parameter change after the last step, and the
    first step's final latents ``x0`` (the whole batch).
    ``keep`` < 1 drops the tail of each batch and averages over the rest,
    the accumulation chunks keeping their size (a planted fault).

    Memory on the device: parameters, the gradient and its accumulator in
    float32 (3 x 2.7 GB at FLUX.1-dev widths and 3 blocks) plus one term's
    activations.  The AdamW moments and the initial parameters wait on the
    host; the update moves one leaf's moments in and out at a time."""
    num = Numerics(quant)
    G, o = cfg["group_size"], cfg["optim"]
    b1, b2 = o["betas"]
    dtype = jnp.dtype(cfg["param_dtype"])
    sde = cfg["trainer_type"] != "awm"
    loss_cfg = dict(cfg, microbatch=max(1, round(cfg["microbatch"] * keep)))
    loop_key = jax.random.PRNGKey(seed)
    p = init_params(cfg, seed)
    p0 = jax.device_get(p)
    m = {k: np.zeros(x.shape, np.float32) for k, x in p0.items()}
    v = {k: np.zeros(x.shape, np.float32) for k, x in p0.items()}

    @jax.jit
    def sample(p, cond, it):
        k_s, k_u = jax.random.split(jax.random.fold_in(loop_key, it))
        cond_g = jnp.repeat(cond, G, axis=0)
        xs = rollout(cfg, num, p, cond_g, k_s, sde)
        x0 = xs[-1]
        adv = group_normalize(rewards(cfg, num, x0, cond_g), G)
        if keep < 1.0:
            n = int(round(cond_g.shape[0] * keep))
            xs, cond_g, adv = xs[:, :n], cond_g[:n], adv[:n]
        return xs, cond_g, adv, k_u, x0

    @jax.jit
    def grad(params, xs, cond_g, adv, k_u):
        term, n = loss_terms(loss_cfg, num, xs, cond_g, adv, k_u)
        vg = jax.value_and_grad(term, has_aux=True)

        def body(acc, i):
            (l, s), g = vg(params, i)
            return jax.tree.map(jnp.add, acc, (l, s, g)), None

        zero = (jnp.zeros((), F32), jnp.zeros((), F32),
                jax.tree.map(jnp.zeros_like, params))
        (l, s, g), _ = jax.lax.scan(body, zero, jnp.arange(n))
        g = jax.tree.map(lambda x: x / n, g)
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        g = jax.tree.map(
            lambda x: x * jnp.minimum(1.0, o["grad_clip"] / (gn + 1e-9)), g)
        return l / n, s / n, g, leaf_norms(g)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def adamw(p, g, m, v, step):
        """One leaf's update."""
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        delta = (m / (1.0 - b1 ** step)) / (
            jnp.sqrt(v / (1.0 - b2 ** step)) + o["eps"]) \
            + o["weight_decay"] * p
        return round_to(dtype, p - o["lr"] * delta), m, v

    per_step = []
    for it, prompts in enumerate(prompt_batches):
        xs, cond_g, adv, k_u, x0 = sample(p, encode_prompts(cfg, prompts),
                                          jnp.int32(it))
        if it == 0:
            first_x0 = np.asarray(jax.device_get(x0))
        del x0
        loss, scale, g, gn = grad(p, xs, cond_g, adv, k_u)
        del xs, cond_g, adv
        step = jnp.float32(it + 1)
        for k in list(p):
            p[k], m_k, v_k = adamw(p[k], g.pop(k), m[k], v[k], step)
            m[k], v[k] = jax.device_get((m_k, v_k))
        per_step.append((loss, scale, gn))
    del m, v
    diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum((a - b) ** 2)))
    per_step, change = jax.device_get(
        (per_step, {k: diff(p[k], p0[k]) for k in p}))
    return {"loss": [float(l) for l, _, _ in per_step],
            "scale": [float(s) for _, s, _ in per_step],
            "grad_norms": [{k: float(x) for k, x in gn.items()}
                           for _, _, gn in per_step],
            "change_norms": {k: float(x) for k, x in change.items()},
            "x0": first_x0}


def first_rollout(cfg: Dict, seed: int, prompts: Sequence[str],
                  quant: str = "") -> np.ndarray:
    """The first step's final latents (B, Lt, ld) alone, as ``run_steps``
    makes them: the rollout from the initial weights."""
    num = Numerics(quant)
    G = cfg["group_size"]
    loop_key = jax.random.PRNGKey(seed)

    @jax.jit
    def go(p, cond):
        k_s, _ = jax.random.split(jax.random.fold_in(loop_key, 0))
        cond_g = jnp.repeat(cond, G, axis=0)
        return rollout(cfg, num, p, cond_g, k_s,
                       cfg["trainer_type"] != "awm")[-1]

    x0 = go(init_params(cfg, seed), encode_prompts(cfg, prompts))
    return np.asarray(jax.device_get(x0))
