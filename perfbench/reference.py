"""Plain float32 NetES reference: the yardstick `correct` is decided by.

A straightforward re-implementation of what one NetES iteration computes
(paper arXiv:1902.06740, Algorithm 1 and Eq. 3), written from the paper and
the configuration files alone. It imports nothing of the program under test
and takes nothing the program made: the graph, the initial parameters, the
noise and the episodes are all drawn here from the seeds, following the
same documented random-number layout (threefry keys split as listed below;
the graph from numpy's PCG64 stream).

Pieces, each plain `jax.numpy` on float32 under matmul precision "highest":

* the Erdős–Rényi graph with the single-component repair, and the fully
  connected graph (self-loops on, symmetric);
* the policy: MLP obs→64→64→act with tanh, on a flat parameter vector;
* the pendulum swing-up task (gym Pendulum-v1 dynamics, 200 steps);
* antithetic perturbation, centered-rank fitness shaping, the Eq. 3
  update with decoupled weight decay, and the broadcast-best select;
* the symmetric absmax int8 channel codec (per message).

Random-number layout (the semantics being checked):

* population: ``key, sub = split(PRNGKey(seed))``; agent a's parameters
  come from ``split(sub, N)[a]``, one ``split`` per layer;
* iteration: ``key, k_eps, k_eval, k_beta = split(key, 4)``; ε is
  one ``normal(k_eps, (N, D))``;
* agent m's episode uses ``split(split(k_eval, N)[m], 1)[0]`` for both
  of its antithetic halves; inside the episode ``k_reset, k_steps =
  split(key)`` and step t uses ``split(k_steps, 200)[t]``;
* the broadcast fires when ``uniform(k_beta) < p_broadcast``.

``dtype`` selects the arithmetic: float32 is the reference, bfloat16 is
the lower-precision control (parameters, noise, episodes and payloads in
bfloat16; reward sums and contractions accumulate in float32).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# --------------------------------------------------------------------------
# graphs
# --------------------------------------------------------------------------


def _component_labels(indptr: np.ndarray, indices: np.ndarray, n: int
                      ) -> np.ndarray:
    """Connected-component label per node; components are numbered in
    the order of their smallest node."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import connected_components
    g = csr_matrix((np.ones(len(indices), np.int8), indices, indptr),
                   shape=(n, n))
    _, labels = connected_components(g, directed=False)
    # renumber by first appearance so component 0 holds node 0, etc.
    _, first = np.unique(labels, return_index=True)
    order = np.argsort(first)
    rank = np.empty_like(order)
    rank[order] = np.arange(len(order))
    return rank[labels]


def erdos_renyi_edges(n: int, p: float, seed: int) -> np.ndarray:
    """Undirected G(n, p) with self-loops, repaired to one component.

    Draws an (n, n) uniform matrix from ``default_rng(seed)`` and keeps
    the strict upper triangle below ``p``. While the graph has several
    components, component 0 gets one bridge to each other component
    c = 1, 2, …: an endpoint drawn uniformly from component 0, then one
    from component c (``rng.choice`` over each component's nodes in
    ascending order). Returns the (E, 2) array of directed (row, col)
    pairs of the symmetric adjacency, self-loops included, sorted.
    """
    rng = np.random.default_rng(seed)
    upper = np.triu(rng.random((n, n)) < p, k=1)
    r, c = np.nonzero(upper)
    del upper
    pairs = {(int(a), int(b)) for a, b in zip(r, c, strict=True)}
    while True:
        edges = _symmetric(n, pairs)
        indptr = np.searchsorted(edges[:, 0], np.arange(n + 1))
        labels = _component_labels(indptr, edges[:, 1], n)
        if labels.max() == 0:
            return edges
        comp0 = np.nonzero(labels == 0)[0]
        for comp in range(1, int(labels.max()) + 1):
            i = int(rng.choice(comp0))
            j = int(rng.choice(np.nonzero(labels == comp)[0]))
            pairs.add((min(i, j), max(i, j)))


def _symmetric(n: int, pairs) -> np.ndarray:
    arr = np.array(sorted(pairs), np.int64).reshape(-1, 2)
    loops = np.stack([np.arange(n), np.arange(n)], axis=1)
    both = np.concatenate([arr, arr[:, ::-1], loops])
    both = np.unique(both, axis=0)       # sorted by row, then column
    return both


def graph_edges(family: str, n: int, p: float, seed: int
                ) -> Optional[np.ndarray]:
    """Edge list of the configuration's graph; None for fully connected
    (every agent hears every agent, its own sample included)."""
    if family == "fully_connected":
        return None
    if family == "erdos_renyi":
        return erdos_renyi_edges(n, p, seed)
    raise ValueError(f"the reference has no graph family {family!r}")


# --------------------------------------------------------------------------
# policy and task
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Mlp:
    """obs → hidden… → act, tanh between layers and on the output."""
    sizes: Sequence[int]

    @property
    def shapes(self):
        out = []
        for din, dout in zip(self.sizes[:-1], self.sizes[1:], strict=True):
            out += [(din, dout), (dout,)]
        return out

    @property
    def dim(self) -> int:
        return int(sum(np.prod(s) for s in self.shapes))

    def init(self, key):
        parts = []
        for shape in self.shapes:
            key, sub = jax.random.split(key)
            if len(shape) == 2:
                std = jnp.sqrt(2.0 / (shape[0] + shape[1]))
                parts.append(std * jax.random.normal(sub, shape).reshape(-1))
            else:
                parts.append(jnp.zeros(shape))
        return jnp.concatenate(parts)

    def apply(self, theta, obs):
        h, off = obs, 0
        n_layers = len(self.sizes) - 1
        for layer in range(n_layers):
            din, dout = self.sizes[layer], self.sizes[layer + 1]
            w = theta[off:off + din * dout].reshape(din, dout)
            off += din * dout
            b = theta[off:off + dout]
            off += dout
            h = jnp.tanh(h @ w + b)
        return h


@dataclasses.dataclass(frozen=True)
class Pendulum:
    """gym Pendulum-v1: reward −(angle² + 0.1·ω² + 0.001·u²) per step."""
    steps: int = 200
    max_speed: float = 8.0
    max_torque: float = 2.0
    dt: float = 0.05
    g: float = 10.0

    def episode(self, policy: Mlp, theta, key, dtype):
        k_reset, k_steps = jax.random.split(key)
        hi = jnp.array([jnp.pi, 1.0])
        s0 = jax.random.uniform(k_reset, (2,), minval=-hi, maxval=hi)
        keys = jax.random.split(k_steps, self.steps)   # noise-free task

        def step(carry, _):
            (th, om), total = carry
            obs = jnp.stack([jnp.cos(th), jnp.sin(th), om / self.max_speed])
            u = jnp.clip(policy.apply(theta, obs)[0], -1, 1) * self.max_torque
            ang = (th + jnp.pi) % (2 * jnp.pi) - jnp.pi
            cost = ang ** 2 + 0.1 * om ** 2 + 0.001 * u ** 2
            om = om + (1.5 * self.g * jnp.sin(th) + 3.0 * u) * self.dt
            om = jnp.clip(om, -self.max_speed, self.max_speed)
            th = th + om * self.dt
            return ((th, om), total - cost.astype(jnp.float32)), None

        s0 = s0.astype(dtype)
        (_, total), _ = jax.lax.scan(
            step, ((s0[0], s0[1]), jnp.zeros((), jnp.float32)), keys)
        return total


TASKS = {"pendulum": Pendulum}
ROW_BLOCK = 4096        # episodes per reward call: bounds device memory
EDGE_BLOCK = 16384      # edges per gather-scatter of the contraction


# --------------------------------------------------------------------------
# one NetES iteration
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Setup:
    """Everything one run of the reference needs, from the config files."""
    n: int
    family: str
    p: float
    topo_seed: int
    sizes: Sequence[int]
    task: str = "pendulum"
    alpha: float = 0.05
    sigma: float = 0.1
    p_broadcast: float = 0.8
    weight_decay: float = 0.005
    quantize_bits: Optional[int] = None


def _quantize(x, bits: int, axis):
    levels = float(2 ** (bits - 1) - 1)
    s = jnp.abs(x).max(axis=axis, keepdims=True) / levels
    q = jnp.round(x / jnp.where(s > 0, s, 1.0))
    return (q * s).astype(x.dtype)


def centered_rank(x):
    ranks = jnp.argsort(jnp.argsort(x))
    return ranks.astype(jnp.float32) / (x.shape[0] - 1) - 0.5


class Reference:
    """The reference for one configuration. ``fault`` plants one of the
    faults the benchmark has to catch, in the reference's own arithmetic
    (to read the number a broken program would give):

    * ``frozen``: the iteration returns its input state unchanged;
    * ``half_batch``: only the first half of the population is
      evaluated; the mean, the shaping and the update use that half;
    * ``no_mixing``: the Eq. 3 neighbor term is dropped (weight decay
      alone updates the parameters);
    * ``wrong_row``: the broadcast sends the worst candidate's
      parameters instead of the best's.
    """

    def __init__(self, setup: Setup, dtype=jnp.float32,
                 fault: Optional[str] = None,
                 edges: Optional[np.ndarray] = None):
        self.s = setup
        self.dtype = jnp.dtype(dtype)
        self.fault = fault
        self.policy = Mlp(tuple(setup.sizes))
        self.task = TASKS[setup.task]()
        self.edges = edges if edges is not None else graph_edges(
            setup.family, setup.n, setup.p, setup.topo_seed)
        self._episodes = jax.jit(self._episodes_impl)

    @property
    def nnz(self) -> int:
        return self.s.n ** 2 if self.edges is None else len(self.edges)

    # -- pieces -------------------------------------------------------------
    def init_thetas(self, seed: int):
        key, sub = jax.random.split(jax.random.PRNGKey(seed))
        thetas = jax.vmap(self.policy.init)(jax.random.split(sub, self.s.n))
        return key, thetas.astype(self.dtype)

    def _episodes_impl(self, params, keys):
        def one(theta, k):
            return self.task.episode(self.policy, theta,
                                     jax.random.split(k, 1)[0], self.dtype)
        return jax.vmap(one)(params, keys)

    def rewards(self, params, k_eval):
        keys = jax.random.split(k_eval, self.s.n)[:params.shape[0]]
        out = []
        for lo in range(0, params.shape[0], ROW_BLOCK):
            hi = lo + ROW_BLOCK
            out.append(self._episodes(params[lo:hi], keys[lo:hi]))
        return jnp.concatenate(out)

    def noise(self, k_eps):
        eps = jax.random.normal(k_eps, (self.s.n, self.policy.dim))
        return eps.astype(self.dtype)

    def neighbor_sum(self, coeff, values):
        """out_j = Σ_i a_ji coeff_i values_i, accumulated in float32."""
        src = coeff[:, None].astype(jnp.float32) * values.astype(jnp.float32)
        if self.edges is None:
            return jnp.broadcast_to(src.sum(axis=0), values.shape)
        out = jnp.zeros(values.shape, jnp.float32)
        for lo in range(0, len(self.edges), EDGE_BLOCK):
            e = jnp.asarray(self.edges[lo:lo + EDGE_BLOCK])
            out = out.at[e[:, 0]].add(src[e[:, 1]])
        return out

    def row_sum(self, coeff):
        if self.edges is None:
            return jnp.full((self.s.n,), coeff.sum())
        e = self.edges
        return jnp.zeros((self.s.n,), jnp.float32).at[e[:, 0]].add(
            coeff[e[:, 1]])

    # -- the iteration -------------------------------------------------------
    def step(self, thetas, key, keep=False):
        """One NetES iteration. Returns (thetas', key', metrics); with
        ``keep`` the metrics also hold the broadcast's candidates (the
        2N perturbed parameter vectors, + half first, through the codec
        where the cell has one) and their returns, as ``candidates`` and
        ``returns``."""
        s, n = self.s, self.s.n
        key_next, k_eps, k_eval, k_beta = jax.random.split(key, 4)
        s_eps = (s.sigma * self.noise(k_eps)).astype(self.dtype)
        pos, neg = thetas + s_eps, thetas - s_eps
        live = n // 2 if self.fault == "half_batch" else n
        r_pos = self.rewards(pos[:live], k_eval)
        r_neg = self.rewards(neg[:live], k_eval)
        raw = jnp.concatenate([r_pos, r_neg])
        shaped_all = centered_rank(raw)
        shaped = jnp.zeros((n,), jnp.float32).at[:live].set(
            shaped_all[:live] - shaped_all[live:])
        wire = pos if s.quantize_bits is None else _quantize(
            pos, s.quantize_bits, axis=1)
        mixed = self.neighbor_sum(shaped, wire)
        mixed = mixed - self.row_sum(shaped)[:, None] * thetas.astype(
            jnp.float32)
        if self.fault == "no_mixing":
            mixed = jnp.zeros_like(mixed)
        update = (s.alpha / (n * s.sigma ** 2)) * mixed \
            - s.weight_decay * thetas.astype(jnp.float32)
        new = (thetas.astype(jnp.float32) + update).astype(self.dtype)
        best = jnp.argmin(raw) if self.fault == "wrong_row" \
            else jnp.argmax(raw)
        cands_row = jnp.where(best < live, pos[best % live], neg[best % live])
        if s.quantize_bits is not None:
            cands_row = _quantize(cands_row, s.quantize_bits, axis=None)
        do_b = jax.random.uniform(k_beta) < s.p_broadcast
        new = jnp.where(do_b, jnp.broadcast_to(cands_row, new.shape), new)
        metrics = {
            "reward_mean": raw.astype(jnp.float32).mean(),
            "update_var": jnp.var(update, axis=0).sum(),
            "broadcast": do_b.astype(jnp.float32),
        }
        if keep:       # as the broadcast would send each
            cands = jnp.concatenate([pos[:live], neg[:live]])
            if s.quantize_bits is not None:
                cands = _quantize(cands, s.quantize_bits, axis=1)
            metrics["candidates"] = cands
            metrics["returns"] = raw
        if self.fault == "frozen":
            return thetas, key, metrics
        return new, key_next, metrics

    def broadcast_flags(self, seed: int, iters: int) -> List[float]:
        """Whether each of the first ``iters`` iterations broadcasts: a
        function of the random stream alone."""
        key = jax.random.split(jax.random.PRNGKey(seed))[0]
        flags = []
        for _ in range(iters):
            key, _, _, k_beta = jax.random.split(key, 4)
            flags.append(float(jax.random.uniform(k_beta)
                               < self.s.p_broadcast))
        return flags

    def run(self, seed: int, iters: int) -> Dict[str, list]:
        """Metrics of the first ``iters`` iterations from ``seed``."""
        out: Dict[str, list] = {}
        with jax.default_matmul_precision("highest"):
            key, thetas = self.init_thetas(seed)
            for _ in range(iters):
                thetas, key, m = self.step(thetas, key)
                for name, v in jax.device_get(m).items():
                    out.setdefault(name, []).append(float(v))
            del thetas
        return out

    def first(self, seed: int) -> Dict:
        """Iteration 0 from ``seed``: its metrics, its candidates and
        their returns (numpy), the population's mean parameters before
        and after it (``theta_mean``, float64) and the first agent's
        parameters after it (``row``)."""
        with jax.default_matmul_precision("highest"):
            key, thetas = self.init_thetas(seed)
            before = _mean_rows(thetas)
            thetas, _, m = self.step(thetas, key, keep=True)
            out = {k: np.asarray(v) for k, v in jax.device_get(m).items()}
            out["theta_mean"] = [before, _mean_rows(thetas)]
            out["row"] = np.asarray(thetas[0].astype(jnp.float32),
                                    np.float64)
            del thetas
        return out


def _mean_rows(thetas) -> np.ndarray:
    """The mean over agents, accumulated in float32, as float64."""
    return np.asarray(thetas.astype(jnp.float32).mean(axis=0), np.float64)
