"""Traffic of kind ``serve``: an ensemble driver with a fixed
number of runs in flight against ``StencilEngine`` with its defaults.

Each request is one member run of ``steps`` fused steps on one of the
traffic's grids. Set-up makes ``members`` initial states per grid on the
device from the seed and keeps them on the host, as an ensemble driver
holds its members. The request order is a seeded shuffle of blocks that
each hold every (grid, member) pair once, so every seed asks for the same
work. One client thread keeps ``clients`` requests in flight: it submits
the next as soon as one comes back, and times each from its submit to its
receipt. The window is ``--seconds`` of wall time; requests that come back
after it are waited for but not counted. A sample of the window's answers,
drawn from the seed and holding one of the largest grid, is compared with
the plain reference at each request's own grid.
"""

from __future__ import annotations

import concurrent.futures as cf
import itertools
import math
import time

import jax
import numpy as np

import common
import tracing
from repro import apps
from repro.serve import StencilEngine, StencilRequest

#: seconds to wait for the requests still in flight when the window closes
DRAIN_S = 60.0


def _order(n_grids: int, members: int, rng):
    """Endless request order: seeded shuffles of all (grid, member) pairs."""
    pairs = [(g, m) for g in range(n_grids) for m in range(members)]
    while True:
        for i in rng.permutation(len(pairs)):
            yield pairs[i]


def run(r) -> None:
    cfg, traffic = r.cell.config, r.cell.traffic
    grids = [r.grid(g) for g in traffic["grids"]]
    steps = r.steps(traffic["steps"])
    members, clients = int(traffic["members"]), int(traffic["clients"])
    program = getattr(apps, cfg["program"])(boundary=cfg["boundary"])
    update = getattr(apps, cfg["update"])(*cfg["update_args"])
    scalars = {k: np.float32(v) for k, v in cfg["scalars"].items()}

    with r.phase("data"):
        pools = [jax.tree.map(np.asarray, common.make_fields(
                     cfg, g, r.seed, members=members)) for g in grids]
        coeffs = [jax.tree.map(np.asarray, common.make_coeffs(cfg, g))
                  for g in grids]

    def request(gi, m):
        return StencilRequest(program=program,
                              fields={f: x[m] for f, x in pools[gi].items()},
                              scalars=scalars, coeffs=coeffs[gi],
                              steps=steps, update=update)

    engine = StencilEngine()
    try:
        with r.phase("warmup"):
            _warm_up(engine, request, len(grids), members, clients)
        r.setup_done()
        _closed_loop(r, engine, request, grids, steps, members, clients)
    finally:
        engine.close()


def _warm_up(engine, request, n_grids, members, clients):
    """Serve, per grid, a batch of every size the closed loop can form:
    the engine pads a batch to a power of two, up to its ``max_batch``.
    A batch that did not form whole is tried again, twice at most."""
    top = int(math.log2(min(clients, engine.max_batch)))
    for gi in range(n_grids):
        for b in (1 << k for k in range(top + 1)):
            for _ in range(3):
                futs = [engine.submit(request(gi, m % members))
                        for m in range(b)]
                if {f.result().batch_size for f in futs} == {b}:
                    break


def _closed_loop(r, engine, request, grids, steps, members, clients):
    rng = np.random.default_rng(r.seed)
    order = _order(len(grids), members, rng)
    keep_every = int(r.cell.traffic["keep_every"])
    keep_offset = int(rng.integers(keep_every))
    largest = max(range(len(grids)), key=lambda g: np.prod(grids[g]))
    inflight, done = {}, []         # future -> (index, grid, member, t_submit)
    counter = itertools.count()
    kept_largest = False

    def submit():
        gi, m = next(order)
        j = next(counter)
        with tracing.span("submit"):
            fut = engine.submit(request(gi, m))
        inflight[fut] = (j, gi, m, time.perf_counter())

    with r.window() as w:
        for _ in range(clients):
            submit()
        while True:
            left = r.seconds - w.elapsed()
            if left <= 0:
                w.close()
                break
            with tracing.span("wait"):
                ready, _ = cf.wait(list(inflight), timeout=left,
                                   return_when=cf.FIRST_COMPLETED)
            now = time.perf_counter()
            for fut in ready:
                j, gi, m, t_sub = inflight.pop(fut)
                res, err = None, fut.exception()
                if err is None:
                    res = fut.result()
                keep = res is not None and (
                    j % keep_every == keep_offset
                    or (gi == largest and not kept_largest))
                kept_largest |= keep and gi == largest
                done.append(dict(grid=gi, member=m, latency=now - t_sub,
                                 in_window=now <= w.t0 + r.seconds,
                                 failed=err is not None,
                                 batch=None if res is None else res.batch_size,
                                 outputs=res.outputs if keep else None))
                if now - w.t0 < r.seconds:
                    submit()
    t_end = w.t0 + r.seconds
    r.read_memory()
    cf.wait(list(inflight), timeout=DRAIN_S)
    late = sum(not f.done() or f.exception() is not None for f in inflight)

    window = [d for d in done if d["in_window"]]
    ok = [d for d in window if not d["failed"]]
    r.attempted = len(window)
    r.failed = len(window) - len(ok)
    points = [int(np.prod(g)) for g in grids]
    r.metrics["serve_gpts_per_s"] = (sum(points[d["grid"]] for d in ok)
                                     * steps / (t_end - w.t0) / 1e9)
    lat = sorted([d["latency"] for d in ok] + [math.inf] * r.failed)
    r.metrics["serve_p95_ms"] = lat[math.ceil(0.95 * len(lat)) - 1] * 1e3
    r.counters.update(batch_mean=float(np.mean([d["batch"] for d in ok])),
                      requests=len(window), late_after_window=late)

    cfg = r.cell.config
    sample = [d for d in ok if d["outputs"] is not None]
    readings = []
    with r.phase("reference"):
        for gi, g in enumerate(grids):
            ref = jax.jit(lambda s, sc, co: r.cell.reference.run(
                s, sc, co, steps, cfg["update_args"]))
            sc = common.make_scalars(cfg)
            co = common.make_coeffs(cfg, g)
            for d in (d for d in sample if d["grid"] == gi):
                start = request(gi, d["member"]).fields
                readings.append(common.compare(
                    cfg, start, d["outputs"], ref(start, sc, co)))
    r.counters["checked"] = len(readings)
    r.readings = common.worst(readings) if readings else {}
