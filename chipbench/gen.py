"""Inputs of a run, made from ``--seed``: the corpus, the conversations'
query vectors, and the open-loop send schedule.

The corpus and the queries follow ``repro.data.synthetic``'s generative
shapes at the configuration's width (a topic-clustered unit-norm corpus
over zipf-popular topics; conversations that drift around a topic and
shift to a new one with probability ``shift_prob`` per turn) and are made
on the device in one jitted call each.  The schedule is made on the host
with numpy: every turn's send time is fixed before the run starts.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Tuple

import numpy as np

# synthetic.py's spreads are set at d = 64; noise norms grow with
# sqrt(d), so they are scaled by sqrt(64 / d) to keep its geometry
_REF_D = 64.0


def prng_key(seed: int, stream: int):
    """A JAX key for ``(seed, stream)``.  ``PRNGKey`` keeps only the low
    32 bits of a seed, so the high bits are folded in: seeds that differ
    above bit 31 give different inputs."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


def _normalize(x):
    import jax.numpy as jnp
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-9)


def corpus(cfg: Dict, seed: int):
    """(topic centres (n_topics, d), docs (n_docs, d)) on the device."""
    import jax
    import jax.numpy as jnp

    n, d, nt = cfg["n_docs"], cfg["d"], cfg["n_topics"]
    spread = cfg["doc_spread"] * math.sqrt(_REF_D / d)
    zipf = cfg["zipf"]

    @jax.jit
    def make(key):
        kc, kt, kn = jax.random.split(key, 3)
        centers = _normalize(jax.random.normal(kc, (nt, d)))
        logits = -zipf * jnp.log(jnp.arange(1, nt + 1, dtype=jnp.float32))
        topic = jax.random.categorical(kt, logits, shape=(n,))
        noise = jax.random.normal(kn, (n, d))
        return centers, _normalize(centers[topic] + spread * noise)

    return make(prng_key(seed, 0))


def conversations(cfg: Dict, centers, seed: int, n_convs: int,
                  turns: int, shift_prob: float):
    """(n_convs, turns, d) query vectors on the device: each conversation
    starts on a random topic, walks around it, and at every turn after
    the first shifts to a new topic with probability ``shift_prob``."""
    import jax
    import jax.numpy as jnp

    d, nt = cfg["d"], cfg["n_topics"]
    scale = math.sqrt(_REF_D / d)
    walk, drift = cfg["walk_step"] * scale, cfg["query_drift"] * scale

    @jax.jit
    def make(key, centers):
        k0, kt = jax.random.split(key)
        topic0 = jax.random.randint(k0, (n_convs,), 0, nt)

        def turn(carry, kk):
            topic, anchor, t = carry
            ks, kn, kw, kq = jax.random.split(kk, 4)
            shift = (t > 0) & (jax.random.uniform(ks, (n_convs,))
                               < shift_prob)
            topic = jnp.where(shift, jax.random.randint(
                kn, (n_convs,), 0, nt), topic)
            anchor = jnp.where(shift[:, None], centers[topic], anchor)
            anchor = _normalize(anchor + walk
                                * jax.random.normal(kw, anchor.shape))
            q = _normalize(anchor + drift
                           * jax.random.normal(kq, anchor.shape))
            return (topic, anchor, t + 1), q

        _, qs = jax.lax.scan(turn, (topic0, centers[topic0], 0),
                             jax.random.split(kt, turns))
        return jnp.swapaxes(qs, 0, 1)

    return make(prng_key(seed, 1), centers)


@dataclasses.dataclass
class Schedule:
    """Every turn of a run, with its send time in seconds from the
    window's opening.

    ``window`` turns are due in ``[0, seconds)`` and sent open loop.
    ``replay`` turns (``t < 0``) belong to conversations still live when
    the window opens; set-up replays them, in time order, so the window
    opens on a stationary population.  ``n_fill`` one-turn conversations
    fill the rest of the session slab first (finished conversations that
    linger until LRU eviction reclaims them).  Turns are
    ``(time, conversation row, turn index)``; filler conversations use
    rows ``n_convs ..`` of the query tensor."""
    n_convs: int
    turns: int                      # query tensor's turn axis
    think_s: float                  # mean think time between turns
    window: np.ndarray              # (n, 3): time, conv, turn
    replay: np.ndarray              # (n, 3)
    n_fill: int
    n_live: int                     # conversations live at t = 0
    prefix: str = ""                # keeps two schedules' ids apart

    def conv_id(self, row: int) -> str:
        if row < self.n_convs:
            return f"{self.prefix}c{row}"
        return f"{self.prefix}f{row - self.n_convs}"


def schedule(traffic: Dict, seed: int, seconds: float,
             n_slots: int, prefix: str = "") -> Schedule:
    """The open-loop schedule of a traffic mix.

    Every seed gets the same work in another order.  Conversations
    arrive as a Poisson process at the rate ``rate / mean turns`` per
    second over the span generated: the gaps between arrivals are the
    quantiles of an exponential, shuffled, and scaled so that the
    arrivals fill the span (a Poisson process with its count fixed and
    its gaps' spread, a coefficient of variation of about 1, the same
    for every seed).  Turn counts are
    spread evenly over ``[turns_min, turns_max]``, think times are the
    quantiles of an exponential with mean ``live_population / rate`` (so
    about ``live_population`` conversations are live at any time), and
    both are shuffled over the conversations.  The span starts early
    enough (``history``) that the conversations live at ``t = 0`` have
    their earlier turns in it.  Last, time is scaled (by about 1%) so
    that exactly ``rate * seconds`` turns fall in the window.
    """
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    rate = float(traffic["rate_turns_per_s"])
    tmin, tmax = int(traffic["turns_min"]), int(traffic["turns_max"])
    mean_turns = (tmin + tmax) / 2.0
    think = (float(traffic["live_population"]) / rate) if tmax > 1 else 0.0
    history = 3.0 * (tmax - 1) * think
    span = history + seconds
    n = int(round(rate / mean_turns * span))
    # n + 1 gaps, so that all n arrivals fall inside the span
    g = rng.permutation(-np.log1p(-(np.arange(n + 1) + 0.5) / (n + 1)))
    start = -history + span * np.cumsum(g)[:n] / g.sum()
    counts = rng.permutation(tmin + np.arange(n) % (tmax - tmin + 1))
    q = (np.arange(n * (tmax - 1)) + 0.5) / max(n * (tmax - 1), 1)
    gaps = rng.permutation(-think * np.log1p(-q)).reshape(n, tmax - 1)
    times = start[:, None] + np.concatenate(
        [np.zeros((n, 1)), np.cumsum(gaps, axis=1)], axis=1)
    times[np.arange(tmax)[None, :] >= counts[:, None]] = np.inf
    due = np.sort(times[(times >= 0) & np.isfinite(times)])
    want = int(round(rate * seconds))
    if len(due) > want:
        times = times * (seconds / ((due[want - 1] + due[want]) / 2.0))

    conv, turn = np.nonzero(np.isfinite(times))
    t = times[conv, turn]
    table = np.stack([t, conv, turn], axis=1)
    table = table[np.argsort(t, kind="stable")]
    in_window = (table[:, 0] >= 0) & (table[:, 0] < seconds)
    last = np.where(np.isfinite(times), times, -np.inf).max(axis=1)
    live = (times[:, 0] < 0) & (last >= 0)
    replay = table[(table[:, 0] < 0) & live[table[:, 1].astype(int)]]
    n_live = int(live.sum())
    # a few more fillers than free slots, so that set-up also runs the
    # eviction path the window will take
    n_fill = max(n_slots - n_live, 0) + 8
    return Schedule(n, tmax, think, table[in_window], replay, n_fill,
                    n_live, prefix)


def replay_order(sched: Schedule) -> List[Tuple[int, int]]:
    """(conversation row, turn) of set-up's turns: the fillers first
    (they are the least recently used), then the live conversations'
    earlier turns in time order."""
    fill = [(sched.n_convs + i, 0) for i in range(sched.n_fill)]
    return fill + [(int(c), int(t)) for _, c, t in sched.replay]


def sample(sched: Schedule, seed: int, n: int) -> np.ndarray:
    """Indices into ``sched.window`` of the turns the check compares, a
    fixed draw from the seed (all of them where fewer are due)."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 11])
    m = len(sched.window)
    return np.sort(rng.choice(m, size=min(n, m), replace=False))
