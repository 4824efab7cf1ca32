"""Plain reference of Decentralized Matrix Factorization (Chen et al., AAAI
2018), written from the paper and independent of the program under test.

* Walk propagation (Eqs. 2-4): same-city users, each linked to its N
  nearest (w = 1), symmetrized; M = I + sum_{d<=D} c^d What^d with What the
  row-normalized adjacency. The graph is block-diagonal by city, so M is
  built per city on the host, in float64 as the paper's sums, then stored
  as a dense (I, I) matrix.
* Algorithm 1 per minibatch, in the dense form: every rating (i, j, r,
  conf) updates u_i, q^i_j by SGD on Eqs. 9-11, and its global-factor
  gradient reaches every receiver i' with weight M[i, i'] (the sender's own
  update is M[i, i] = 1): P[:, j] -= lr * M[i, :]^T gp. With DP the message
  gp is L2-clipped to C and gets sigma*C Gaussian noise from the counter
  stream before it is sent.
* The epoch's sample stream: the positives shuffled, m uniform negatives
  per positive with confidence 1/m, the whole shuffled again, cut to whole
  batches; with DP a fresh per-epoch noise seed is drawn after the
  sampling. The stream follows from the job's seed alone.
* Serving: the user's home-city POIs, minus those seen in training, ranked
  by u_i . (p^i_j + q^i_j), ties to the lower id; users with no training
  check-in, or an empty home city, get the most-checked-in POIs.

``dtype`` sets the precision of state and arithmetic: float32 is the
reference, bfloat16 the control that `correct` has to reject.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

GOLDEN = 0x9E3779B9
M1, M2 = 0x21F0AAAD, 0x735A2D97
KMAX = 256


# ----------------------------------------------------------------- graph
def walk_matrix(coords: np.ndarray, city: np.ndarray, n_neighbors: int,
                walk_length: int, hop_damping: float = 1.0) -> np.ndarray:
    """(I, I) float32 propagation matrix M of Eqs. 2-4 (uniform weights)."""
    I = len(city)
    coords = np.asarray(coords, np.float32)
    M = np.zeros((I, I), np.float32)
    for c in np.unique(city):
        members = np.flatnonzero(city == c)
        n = len(members)
        pts = coords[members]
        dist = np.sqrt(np.maximum(
            np.sum((pts[:, None, :] - pts[None, :, :]) ** 2, axis=-1), 0.0))
        np.fill_diagonal(dist, np.inf)
        W = np.zeros((n, n), np.float32)
        take = min(n_neighbors, n - 1)
        if take > 0:
            near = np.argsort(dist, axis=1)[:, :take]
            W[np.repeat(np.arange(n), take), near.reshape(-1)] = 1.0
        W = np.maximum(W, W.T)
        deg = W.sum(axis=1, keepdims=True)
        What = np.where(deg > 0, W / np.maximum(deg, 1e-12), 0.0)
        What = What.astype(np.float32).astype(np.float64)
        acc, Wd = np.eye(n), np.eye(n)
        for d in range(1, walk_length + 1):
            Wd = Wd @ What
            acc += hop_damping ** d * Wd
        M[np.ix_(members, members)] = acc.astype(np.float32)
    return M


def walk_for(config: dict, dataset) -> np.ndarray:
    g = config["graph"]
    if not g["uniform_weights"]:
        raise ValueError("the reference builds the paper's w = 1 graph only")
    return walk_matrix(dataset.user_coords, dataset.user_city,
                       g["n_neighbors"], g["walk_length"], g["hop_damping"])


def fanout(M: np.ndarray) -> np.ndarray:
    """(I,) receivers of each sender's message, itself included."""
    return (np.asarray(M) != 0).sum(axis=1)


# --------------------------------------------------------- sample stream
def epoch_stream(rng: np.random.Generator, train: np.ndarray, n_items: int,
                 m: int, batch: int):
    """One epoch's (nb, B) users, items, ratings and confidences."""
    pos = train[rng.permutation(len(train))]
    n = len(pos)
    ui = np.concatenate([pos[:, 0], np.repeat(pos[:, 0], m)])
    vj = np.concatenate([pos[:, 1], rng.integers(0, n_items, size=n * m)])
    r = np.concatenate([np.ones(n, np.float32), np.zeros(n * m, np.float32)])
    conf = np.concatenate([np.ones(n, np.float32),
                           np.full(n * m, 1.0 / m, np.float32)])
    order = rng.permutation(len(ui))
    nb = len(ui) // batch
    cut = nb * batch
    return tuple(a[order][:cut].reshape(nb, batch) for a in (ui, vj, r, conf))


def epoch_noise_seed(rng: np.random.Generator, dp_seed: int) -> int:
    draw = int(rng.integers(0, 2**31 - 1))
    return ((dp_seed * GOLDEN + draw) % 2**32) & 0x7FFFFFFF


def _mix(x):
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(M1)
    x = x ^ (x >> np.uint32(15))
    x = x * np.uint32(M2)
    return x ^ (x >> np.uint32(15))


def counter_normals(seed: int, n_rows: int, k: int) -> np.ndarray:
    """(n_rows, k) standard normals of message rows 0..n_rows-1: counters
    2(row*KMAX + col) and +1 hashed to two uniforms, one Box-Muller draw."""
    with np.errstate(over="ignore"):
        s = _mix(np.uint32(seed))
        rid = np.arange(n_rows, dtype=np.uint32)[:, None]
        col = np.arange(k, dtype=np.uint32)[None, :]
        s_row = _mix(s ^ ((rid >> np.uint32(23)) * np.uint32(GOLDEN)
                          + np.uint32(1)))
        base = ((rid & np.uint32(0x7FFFFF)) * np.uint32(2 * KMAX)
                + col * np.uint32(2))
        h1 = _mix(base ^ s_row)
        h2 = _mix((base + np.uint32(1)) ^ (s_row * np.uint32(GOLDEN)))
    u1 = ((h1 >> np.uint32(8)).astype(np.float64) + 1.0) * 2.0**-24
    u2 = (h2 >> np.uint32(8)).astype(np.float64) * 2.0**-24
    z = np.sqrt(-2.0 * np.log(u1)) * np.cos(2.0 * np.pi * u2)
    return z.astype(np.float32)


# -------------------------------------------------------------- training
@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _step(U, P, Q, M, ui, vj, r, conf, noise, hyper):
    lr, alpha, beta, gamma, clip = hyper
    u, p, q = U[ui], P[ui, vj], Q[ui, vj]
    v = p + q
    e = conf * (r - jnp.sum(u * v, axis=-1))
    gu = -e[:, None] * v + alpha * u
    gp = -e[:, None] * u + beta * p
    gq = -e[:, None] * u + gamma * q
    loss = 0.5 * jnp.sum(conf * (r - jnp.sum(u * v, axis=-1)) ** 2)
    norm = jnp.sqrt(jnp.sum(gp * gp, axis=-1, keepdims=True))
    gp = gp * jnp.minimum(1.0, clip / norm) + noise
    U = U.at[ui].add(-lr * gu)
    Q = Q.at[ui, vj].add(-lr * gq)
    P = P.at[:, vj].add(-lr * M[ui].T[:, :, None] * gp[None, :, :])
    return U, P, Q, loss


@jax.jit
def leaf_norms(U, P, Q, U0):
    """Norms of each leaf's change from the start (P and Q start at 0)."""
    f32 = jnp.float32
    sq = lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(f32))))
    return jnp.stack([sq(U.astype(f32) - U0), sq(P), sq(Q)])


@jax.jit
def leaf_dists(U, P, Q, U2, P2, Q2):
    f32 = jnp.float32
    d = lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a.astype(f32) - b.astype(f32))))
    return jnp.stack([d(U, U2), d(P, P2), d(Q, Q2)])


class Trainer:
    """The reference's training job on the configuration's data."""

    FAULTS = ("half_batch", "no_exchange")

    def __init__(self, config: dict, traffic: dict, dataset, M: np.ndarray,
                 dtype=jnp.float32, fault: str | None = None):
        """``fault`` plants one of `FAULTS` in the reference, to read what
        a broken program would: half of each batch left out and the mean
        taken over the rest, or no message to any learner but the sender."""
        assert fault is None or fault in self.FAULTS, fault
        self.model = config["model"]
        self.dp = traffic.get("dp") or {}
        self.ds = dataset
        self.M = M
        if fault == "no_exchange" and M is not None:
            self.M = np.diag(np.diag(M))
        self.fault = fault
        self.dtype = dtype

    def init_users(self, job_seed: int) -> tuple[np.random.Generator, np.ndarray]:
        rng = np.random.default_rng(job_seed)
        I, K = self.ds.n_users, self.model["dim"]
        U0 = rng.normal(0, self.model["init_scale"], (I, K)).astype(np.float32)
        return rng, U0

    def run(self, job_seed: int, epochs: int) -> dict:
        """Losses, the leaf norms of the change after the first epoch and
        after ``epochs``, and the final state (on the device)."""
        mdl, dt = self.model, self.dtype
        I, J, K = self.ds.n_users, self.ds.n_items, mdl["dim"]
        rng, U0 = self.init_users(job_seed)
        clip = float(self.dp.get("clip", math.inf))
        std = float(self.dp.get("sigma", 0.0)) * clip if self.dp else 0.0
        hyper = tuple(jnp.asarray(x, dt) for x in (
            mdl["lr"], mdl["alpha"], mdl["beta"], mdl["gamma"],
            clip if math.isfinite(clip) else np.finfo(np.float32).max))
        with jax.default_matmul_precision("highest"):
            M = jnp.asarray(self.M, dt)
            U = jnp.asarray(U0, dt)
            P = jnp.zeros((I, J, K), dt)
            Q = jnp.zeros((I, J, K), dt)
            U0d = jnp.asarray(U0)
            out = {"losses": []}
            for t in range(epochs):
                ui, vj, r, conf = epoch_stream(
                    rng, self.ds.train, J, mdl["neg_samples"],
                    mdl["batch_size"])
                nb, B = ui.shape
                if self.fault == "half_batch":
                    conf = np.concatenate(
                        [2.0 * conf[:, :B // 2], 0.0 * conf[:, B // 2:]], 1)
                if self.dp:
                    seed = epoch_noise_seed(rng, int(self.dp.get("dp_seed", 0)))
                    Z = (std * counter_normals(seed, nb * B, K)).reshape(nb, B, K)
                else:
                    Z = np.zeros((nb, B, K), np.float32)
                total = 0.0
                for b in range(nb):
                    U, P, Q, loss = _step(
                        U, P, Q, M, jnp.asarray(ui[b]), jnp.asarray(vj[b]),
                        jnp.asarray(r[b], dt), jnp.asarray(conf[b], dt),
                        jnp.asarray(Z[b], dt), hyper)
                    total += float(loss)
                out["losses"].append(total / (nb * B))
                if t == 0:
                    out["d1"] = np.asarray(leaf_norms(U, P, Q, U0d), np.float64)
            out["d_end"] = np.asarray(leaf_norms(U, P, Q, U0d), np.float64)
            out["state"] = (U, P, Q)
        return out


# --------------------------------------------------------------- serving
def popularity_slate(train: np.ndarray, n_items: int, k: int):
    counts = np.bincount(train[:, 1], minlength=n_items).astype(np.int64)
    top = np.argsort(-counts, kind="stable")[:k]
    peak = max(int(counts.max()), 1)
    return top.astype(np.int32), (counts[top] / peak).astype(np.float32)


class Server:
    """Reference slates for a sample of requests over the factors U, P, Q."""

    def __init__(self, dataset, k: int):
        ds = self.ds = dataset
        self.k = k
        self.seen = np.zeros((ds.n_users, ds.n_items), bool)
        self.seen[ds.train[:, 0], ds.train[:, 1]] = True
        self.has_train = self.seen.any(axis=1)
        self.by_city = {int(c): np.flatnonzero(ds.item_city == c)
                        for c in np.unique(ds.item_city)}
        self.pop_ids, self.pop_vals = popularity_slate(ds.train, ds.n_items, k)

    def candidates(self, users: np.ndarray):
        """(n, C) eligible POI ids (home city, unseen), -1 padded, and the
        rows that take the popularity slate."""
        rows, fallback = [], np.zeros(len(users), bool)
        for n, u in enumerate(users):
            items = self.by_city.get(int(self.ds.user_city[u]), np.zeros(0, int))
            if not self.has_train[u] or len(items) == 0:
                fallback[n] = True
                rows.append(np.zeros(0, int))
                continue
            rows.append(items[~self.seen[u, items]])
        C = max(1, max(len(r) for r in rows))
        cand = np.full((len(users), C), -1, np.int64)
        for n, r in enumerate(rows):
            cand[n, :len(r)] = r
        return cand, fallback

    @staticmethod
    def scores(U, P, Q, users, cand, dtype=jnp.float32, block: int = 512):
        """(n, C) scores u . (p + q) of the candidates in ``dtype`` (-inf on
        padding) and each row's magnitude max_j sum_k |u_k v_jk| in f32."""
        out_s, out_m = [], []
        for s in range(0, len(users), block):
            u = jnp.asarray(users[s:s + block])
            c = jnp.asarray(cand[s:s + block])
            sc, mag = _window_scores(U, P, Q, u, c, dtype)
            out_s.append(np.asarray(sc, np.float32))
            out_m.append(np.asarray(mag, np.float64))
        return np.concatenate(out_s), np.concatenate(out_m)

    def topk(self, scores: np.ndarray, cand: np.ndarray) -> np.ndarray:
        """(n, k) ids by score, ties to the lower id, -1 where none is left."""
        out = np.full((len(cand), self.k), -1, np.int64)
        for n in range(len(cand)):
            ok = cand[n] >= 0
            ids, sc = cand[n][ok], scores[n][ok]
            order = np.lexsort((ids, -sc.astype(np.float64)))[:self.k]
            out[n, :len(order)] = ids[order]
        return out


@functools.partial(jax.jit, static_argnames=("dtype",))
def _window_scores(U, P, Q, u, cand, dtype):
    safe = jnp.maximum(cand, 0)
    uu = U[u].astype(dtype)
    v = P[u[:, None], safe].astype(dtype) + Q[u[:, None], safe].astype(dtype)
    sc = jnp.sum(uu[:, None, :] * v, axis=-1)
    sc = jnp.where(cand >= 0, sc.astype(jnp.float32), -jnp.inf)
    mag = jnp.sum(jnp.abs(U[u])[:, None, :] * jnp.abs(P[u[:, None], safe]
                                                     + Q[u[:, None], safe]), -1)
    return sc, jnp.max(jnp.where(cand >= 0, mag, 0.0), axis=1)
