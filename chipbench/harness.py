"""One run of one cell: set-up, the open-loop window, the check.

Set-up (``setup_s``, from process start to the window's opening):
generate the corpus and the queries on the device from the seed, build
the index, place the engine, then bring the session population to its
steady state — one-turn conversations fill the slab's finished share,
then the conversations live at the window's opening replay their earlier
turns in closed batches.  Every batch shape the window can launch is
run here, so nothing compiles inside the window.

The window drives ``ReplicatedSearchEngine.submit`` with the
configuration's ``replicas`` (1 where it states none), each on a chip
of its own where there are as many: their pump threads are the serving
loop, and the router pins each conversation to one replica.  Turns are
sent at their scheduled times whatever has come back, from this
process's main thread.

After the window every due turn is waited for (a minute at most), the
device's memory is read, the engine is closed and freed, and the
reference checks a sample of the window's answers.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from chipbench import gen, reference, registry, tracing, window
from chipbench.peaks import lookup

#: how long after the window's close a due turn is still waited for
ANSWER_WAIT_S = 60.0
#: length of the traced part of a ``--trace 1`` window, in its middle
TRACE_S = 3.0
#: the Pallas kernel of the IVF-PQ list scan, as named in the trace
ADC_KERNEL = "pq_adc"


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Observations:
    """What the per-layer metric readers read (``metrics/<name>.py``)."""
    config: Dict
    records: List                    # TurnRecords of the window's turns
    replica: List[int]               # the replica that served each record
    batch_sizes: List[int]           # real rows of the window's launches
    padded_sizes: List[int]          # rows launched, padding included
    build_s: float
    peaks: Dict[str, float]
    trace: Optional[Dict] = None     # tracing.reduce() of the traced part
    traced_records: List = dataclasses.field(default_factory=list)
    window: Dict = dataclasses.field(default_factory=dict)  # window.summary


class GcPauses:
    """Durations of the cyclic garbage collector's passes while
    ``armed``: a pass stops every Python thread, the sender's and the
    serving loop's alike."""

    def __init__(self):
        self.armed = False
        self.pauses: List[float] = []
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info) -> None:
        if not self.armed:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t:
            self.pauses.append(time.perf_counter() - self._t)

    def close(self) -> None:
        gc.callbacks.remove(self._on)


class StallWatch:
    """Stops of the whole process in the window.  A thread wakes every
    ``TICK_S`` and notes each wake that came ``STALL_S`` or more late: a
    thread that holds the interpreter lock in a blocking call stops the
    sender and the serving loop alike, and every turn due meanwhile
    waits for it."""

    TICK_S = 0.05
    STALL_S = 0.1

    def __init__(self):
        import threading
        self.stalls: List[tuple] = []     # (seconds from t0, length)
        self._stop = threading.Event()
        self._t = threading.Thread(target=self._run, name="stall-watch",
                                   daemon=True)
        self._t0 = 0.0

    def start(self, t0: float) -> None:
        self._t0 = t0
        self._t.start()

    def _run(self) -> None:
        last = time.perf_counter()
        while not self._stop.wait(self.TICK_S):
            now = time.perf_counter()
            if now - last >= self.TICK_S + self.STALL_S:
                self.stalls.append((last - self._t0,
                                    now - last - self.TICK_S))
            last = now

    def close(self) -> None:
        self._stop.set()
        if self._t.is_alive():
            self._t.join()


class CompileCounter:
    """Counts programs lowered while ``armed`` (a compile, or a load
    from the persistent cache): a new shape inside the window."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        from jax import monitoring
        self.armed = False
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw) -> None:
        if self.armed and name == self.EVENT:
            self.count += 1


def replicas(cfg: Dict) -> int:
    """The configuration's one-chip replicas behind the router."""
    return int(cfg.get("replicas", 1))


def slots(cfg: Dict) -> int:
    """Session slots of the whole deployment: every replica's slab."""
    return cfg["n_slots"] * replicas(cfg)


def chips(router) -> List:
    """The devices the router's replicas serve from, each once: a
    replica's corpus mesh, or the default device where it has none."""
    import jax
    devs = [d for e in router.engines
            for d in (e.mesh.devices.flat if e.mesh is not None
                      else jax.devices()[:1])]
    return list(dict.fromkeys(devs))


def fullest(devices) -> Tuple[int, int]:
    """(bytes in use, peak bytes in use) of the fullest of ``devices``,
    each read on its own chip; 0 where the backend keeps no count."""
    stats = [d.memory_stats() or {} for d in devices]
    return (max(s.get("bytes_in_use", 0) for s in stats),
            max(s.get("peak_bytes_in_use", 0) for s in stats))


def inputs(cfg: Dict, traffic: Dict, seed: int, seconds: float):
    """(schedule, corpus on the device, every turn's query on the host)
    of one run."""
    import jax
    sched = gen.schedule(traffic, seed, seconds, slots(cfg))
    rows = sched.n_convs + slots(cfg) + 8         # fixed shape per cell
    centers, docs = gen.corpus(cfg, seed)
    qdev = gen.conversations(cfg, centers, seed, rows, sched.turns,
                             traffic["shift_prob"])
    return sched, docs, np.asarray(jax.device_get(qdev))


def sampled_queries(sched: gen.Schedule, queries: np.ndarray,
                    idx: np.ndarray) -> np.ndarray:
    return queries[sched.window[idx, 1].astype(int),
                   sched.window[idx, 2].astype(int)]


def build_index(cfg: Dict, docs, seed: int):
    """The configuration's index, built on the device (blocks until
    ready).  Returns (index, keyword for the engine)."""
    import jax
    from repro.core import ivf, pq
    index = ivf.build(docs, cfg["p"], iters=cfg["kmeans_iters"],
                      key=gen.prng_key(seed, 2),
                      capacity_factor=cfg["capacity_factor"])
    if cfg["backend"] == "ivf":
        jax.block_until_ready(index)
        return index, "ivf_index"
    pq_index = pq.build_ivf_pq(index, docs, cfg["pq_m"],
                               iters=cfg["pq_iters"],
                               key=gen.prng_key(seed, 3))
    del index
    jax.block_until_ready(pq_index)
    return pq_index, "ivf_pq_index"


def make_engine(cfg: Dict, **index_kw):
    from repro.serving import ReplicatedSearchEngine, ServingConfig
    scfg = ServingConfig(backend=cfg["backend"], strategy=cfg["strategy"],
                         k=cfg["k"], nprobe=cfg["nprobe"], h=cfg["h"],
                         alpha=cfg["alpha"], rerank=cfg.get("rerank", 64),
                         cache_threshold=0.0)
    return ReplicatedSearchEngine(
        scfg, replicas=replicas(cfg), n_slots=cfg["n_slots"],
        max_batch=cfg["max_batch"], max_wait_s=cfg["max_wait_s"],
        max_inflight=cfg["max_inflight"], **index_kw)


def warm_up(router, sched: gen.Schedule, queries: np.ndarray,
            max_batch: int, replicas: int = 1) -> int:
    """Set-up's closed batches: one wave of every bucket size on every
    replica first (fillers, which the router pins in turn, so each of
    ``b x replicas`` lands ``b`` on each), then the rest of the fillers
    and the replayed turns.  Returns the number of turns served."""
    order = gen.replay_order(sched)
    served, i = 0, 0
    b = 1
    while b <= max_batch and i < len(order):
        for c, t in order[i:i + b * replicas]:
            router.submit(sched.conv_id(c), queries[c, t])
        served += router.drain()
        i += b * replicas
        b *= 2
    step = max_batch * replicas
    for j in range(i, len(order), step):
        for c, t in order[j:j + step]:
            router.submit(sched.conv_id(c), queries[c, t])
        served += router.drain()
    return served


def drive(router, sched: gen.Schedule, queries: np.ndarray,
          seconds: float, on_open: Callable[[float], None] = lambda t: None
          ) -> Dict:
    """Send the window's turns at their times; returns host-clock
    arrays (seconds from the window's opening) and the futures."""
    n = len(sched.window)
    due = sched.window[:, 0]
    done = np.full(n, np.nan)
    sent = np.full(n, np.nan)
    futs = [None] * n
    t0 = time.perf_counter() + 0.01

    def mark(i, fut) -> None:
        t = time.perf_counter() - t0
        if fut.exception() is None:
            done[i] = t

    on_open(t0)
    for i in range(n):
        wait = t0 + due[i] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        _, c, t = sched.window[i]
        c, t = int(c), int(t)
        sent[i] = time.perf_counter() - t0
        fut = router.submit(sched.conv_id(c), queries[c, t])
        fut.add_done_callback(lambda f, i=i: mark(i, f))
        futs[i] = fut
    rest = t0 + seconds - time.perf_counter()
    if rest > 0:
        time.sleep(rest)
    return {"t0": t0, "due": due, "done": done, "sent": sent,
            "futures": futs}


def wait_all(futs, deadline: float) -> None:
    for f in futs:
        left = deadline - time.perf_counter()
        if left <= 0:
            return
        try:
            f.result(timeout=left)
        except Exception:   # noqa: BLE001  counted as failed, not raised
            pass


def check(docs, queries: np.ndarray, answers: List, cfg: Dict
          ) -> Dict[str, Dict[str, float]]:
    """The numbers ``correct`` is decided on, each beside its limit.
    ``answers`` holds (scores, ids) per sampled turn, or None where the
    turn never answered."""
    ok = [j for j, a in enumerate(answers) if a is not None]
    out = {"unanswered": {"value": float(len(answers) - len(ok)),
                          "limit": 0.0}}
    if ok:
        got = reference.compare(
            docs, queries[ok], np.stack([answers[j][0] for j in ok]),
            np.stack([answers[j][1] for j in ok]), cfg["k"])
        for name, v in got.items():
            if name in cfg["limits"]:
                out[name] = {"value": v, "limit": cfg["limits"][name]}
            else:
                log(f"read {name} {v!r}")
    return out


def run_cell(cell: Dict, cfg: Dict, traffic: Dict, *, seed: int,
             seconds: float, trace: bool, end_to_end: List[Dict],
             per_layer: List[Dict], out_dir: str, proc_start: float
             ) -> Dict:
    """One run; returns the contract's result object (without printing).

    ``end_to_end`` and ``per_layer`` are the cell's metric entries of
    ``BENCHMARK.json``: an untraced run reports the first, a traced run
    the second.  Nothing here checks the platform (``run.py`` does)."""
    import threading
    import jax

    dev = jax.devices()[0]
    peaks = lookup(dev.device_kind) if dev.platform == "tpu" else {}
    compiles = CompileCounter()

    sched, docs, queries = inputs(cfg, traffic, seed, seconds)
    log(f"schedule: {len(sched.window)} turns due in {seconds}s, "
        f"{len(sched.replay)} replayed, {sched.n_fill} fillers, "
        f"{sched.n_live} live at opening, think {sched.think_s:.3f}s")

    # -- index and engine ----------------------------------------------
    tb = time.perf_counter()
    index, kw = build_index(cfg, docs, seed)
    build_s = time.perf_counter() - tb
    del docs
    router = make_engine(cfg, **{kw: index})
    del index
    engines = router.engines
    used = chips(router)
    warm = warm_up(router, sched, queries, cfg["max_batch"],
                   replicas(cfg))
    log(f"build {build_s:.3f}s; warm-up served {warm} turns; slab "
        + "; ".join(str(e.store.stats()) for e in engines))

    # -- window ---------------------------------------------------------
    r0 = [len(e.records) for e in engines]
    b0 = [len(e.batcher.batch_sizes) for e in engines]
    tracer = None
    if trace:
        shutil.rmtree(os.path.join(out_dir, "trace"), ignore_errors=True)
        tracer = tracing.Background(os.path.join(out_dir, "trace"),
                                    TRACE_S)
    opened = {}
    watch = StallWatch()

    def on_open(t0: float) -> None:
        opened["setup_s"] = t0 - proc_start
        compiles.armed = pauses.armed = True
        watch.start(t0)
        if tracer is not None:
            # the capture opens mid-window, on a thread of its own
            timer = threading.Timer(max(0.0, seconds / 2 - TRACE_S / 2),
                                    tracer.start)
            timer.start()
            opened["timer"] = timer

    # what set-up left on the heap (the inputs, the warm-up's records,
    # JAX's traces) is kept out of the collector's passes in the window
    gc.collect()
    gc.freeze()
    pauses = GcPauses()
    router.start()
    res = drive(router, sched, queries, seconds, on_open)
    compiles.armed = pauses.armed = False
    watch.close()
    pauses.close()
    b1 = [len(e.batcher.batch_sizes) for e in engines]
    t0 = res["t0"]
    if tracer is not None:
        opened["timer"].join()
        tracer.join()
    wait_all(res["futures"], t0 + seconds + ANSWER_WAIT_S)
    router.close()
    gc.unfreeze()
    # read once the engine is quiet: what serving keeps resident, not
    # the temporaries of whichever wave happened to be in flight; the
    # fullest of the cell's chips
    in_use, peak = fullest(used)

    e2e = window.summary(res["due"], res["done"], seconds)
    late = res["sent"] - res["due"]
    log(f"window: {len(res['due'])} due, {e2e['unanswered']} unanswered; "
        f"generator late p95 {np.percentile(late, 95) * 1e3:.3f} ms, max "
        f"{late.max() * 1e3:.3f} ms at {res['due'][np.argmax(late)]:.3f}s; "
        f"programs lowered in the window: {compiles.count}; collector "
        f"passes {len(pauses.pauses)}, longest "
        f"{max(pauses.pauses, default=0.0) * 1e3:.3f} ms, total "
        f"{sum(pauses.pauses) * 1e3:.3f} ms")
    log(f"stops of {watch.STALL_S * 1e3:.0f} ms or more in the window: "
        + (", ".join(f"{d * 1e3:.3f} ms at {t:.3f}s"
                     for t, d in watch.stalls) or "none"))

    idx = gen.sample(sched, seed, cfg["check_turns"])
    answers = []
    for i in idx:
        f = res["futures"][i]
        ok = f.done() and f.exception() is None
        answers.append(tuple(np.asarray(a) for a in f.result())
                       if ok else None)
    # the window's records and launches, replica by replica
    per = [e.records[n:] for e, n in zip(engines, r0)]
    records = [r for rs in per for r in rs]
    obs = Observations(
        cfg, records, [k for k, rs in enumerate(per) for _ in rs],
        [n for e, lo, hi in zip(engines, b0, b1)
         for n in e.batcher.batch_sizes[lo:hi]],
        [n for e, lo, hi in zip(engines, b0, b1)
         for n in e.batcher.padded_sizes[lo:hi]],
        build_s, peaks, window=dict(e2e))
    if len(engines) > 1:
        log("window's turns by replica: "
            + ", ".join(str(len(rs)) for rs in per))
    if tracer is not None:
        ev = tracing.events(tracing.xplane_path(tracer.log_dir),
                            chips=len(used))
        obs.trace = tracing.reduce(ev, kernels=(ADC_KERNEL,))
        # the waves the trace holds: turns launched inside its span, on
        # the host clock (the trace's own clock starts at its opening)
        lo = tracer.opened - t0
        hi = lo + obs.trace["window_s"]
        sent_at = {(sched.conv_id(int(c)), int(t)): s
                   for (_, c, t), s in zip(sched.window, res["sent"])}
        obs.traced_records = [
            r for r in records
            if lo <= sent_at.get((r.conv_id, r.turn), -1e9)
            + r.queue_wait_s <= hi]
    del router, engines, per, res
    gc.collect()

    # -- reference, once the program's state is freed -------------------
    tr = time.perf_counter()
    _, docs = gen.corpus(cfg, seed)
    readings = check(docs, sampled_queries(sched, queries, idx), answers,
                     cfg)
    del docs
    log(f"reference: {time.perf_counter() - tr:.3f}s for {len(idx)} turns")
    log("compared: " + ", ".join(f"{k} {v['value']!r} (limit {v['limit']!r})"
                                 for k, v in readings.items()))

    metrics: Dict[str, Dict] = {}
    if trace:
        for m in per_layer:
            v = registry.reader(m["name"])(obs)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        e2e.update(setup_s=opened["setup_s"],
                   hbm_in_use_gib=in_use / 2 ** 30)
        for m in end_to_end:
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(used), "memory_peak_bytes": int(peak)}
    out = {"correct": all(r["value"] <= r["limit"]
                          for r in readings.values()),
           "attempted": int(len(sched.window)),
           "failed": int(e2e["unanswered"]),
           "metrics": metrics, "device": device}
    if obs.trace is not None:
        # busy seconds averaged over the chips used; the traced window
        # is the longest of theirs (one chip: its own two numbers)
        device.update(busy_s=float(np.mean([c["busy_s"] for c in
                                            obs.trace["chips"]])),
                      window_s=max(c["window_s"] for c in
                                   obs.trace["chips"]))
        out["breakdown"] = obs.trace["breakdown"]
    out["compared"] = readings
    return out
