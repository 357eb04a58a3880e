"""The comparison that decides `correct`.

Every simulated statistic of a `SimResult` repeats exactly for a given
deployment, traffic and seed, so the program's answer must equal the
reference's value for value: scalars, per-worker arrays, and, where the
flight recorder is on, the event ring, the time series and the sojourn
percentiles. The one field left out is `events`, the stepper's loop count,
which says how the answer was stepped and not what it simulated. The
compared number is how many values differ; its limit is 0. A sweep's
answer is a list of SimResults, one per grid point, compared point by
point.

Beside the reference, checks that go through no simulator at all: the
closed form of a drained job, and, for open-loop requests, the ledger of
every request recomputed in numpy from the recorder's ring and from the
candidate stream replayed on the host.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench.reference import tracing as rtr

NOT_COMPARED = ("events",)


def _fields(x) -> dict | None:
    if hasattr(x, "_fields"):                       # NamedTuple
        return {f: getattr(x, f) for f in x._fields}
    if dataclasses.is_dataclass(x) and not isinstance(x, type):
        return {f.name: getattr(x, f.name) for f in dataclasses.fields(x)}
    if isinstance(x, dict):
        return dict(x)
    return None


def _count(a, b) -> int:
    fa, fb = _fields(a), _fields(b)
    if fa is not None or fb is not None:
        if fa is None or fb is None:
            return 1
        n = sum(_count(fa[k], fb[k]) for k in fa.keys() & fb.keys())
        return n + len(fa.keys() ^ fb.keys())
    if a is None or b is None:
        return int(a is not b)
    xa, xb = np.asarray(a), np.asarray(b)
    if xa.shape != xb.shape:
        return max(xa.size, xb.size, 1)
    if xa.dtype.kind == "f" or xb.dtype.kind == "f":
        same = (xa == xb) | (np.isnan(xa.astype(float))
                             & np.isnan(xb.astype(float)))
    else:
        same = xa == xb
    return int(np.size(same) - np.count_nonzero(same))


def differing_values(got, want) -> int:
    """Values that differ between two SimResults, `events` left out; for
    two sweeps' lists of SimResults, the sum over their points, each point
    that one list has and the other lacks counting as one."""
    if isinstance(got, list) or isinstance(want, list):
        if not (isinstance(got, list) and isinstance(want, list)):
            return 1
        return (sum(differing_values(g, w) for g, w in zip(got, want))
                + abs(len(got) - len(want)))
    return _count(got._replace(**{k: 0 for k in NOT_COMPARED}),
                  want._replace(**{k: 0 for k in NOT_COMPARED}))


def closed_form_misses(result, expect: dict | None) -> int:
    """1 where the result or node count misses the closed form."""
    if not expect:
        return 0
    return int(any(int(getattr(result, k)) != v for k, v in expect.items()))


def trace_dropped(result) -> int:
    return int(result.trace.dropped) if result.trace is not None else 0


def nearest_rank(values: np.ndarray) -> dict | None:
    """count, p50, p90, p99, p999, mean and max of `values`, percentiles by
    nearest rank."""
    v = np.sort(np.asarray(values, np.int64))
    n = int(v.size)
    if n == 0:
        return None
    out = {"count": n, "mean": float(v.sum()) / n, "max": int(v[-1])}
    for name, q in (("p50", 50), ("p90", 90), ("p99", 99), ("p999", 99.9)):
        out[name] = int(v[max(int(np.ceil(q * n / 100.0)), 1) - 1])
    return out


def request_ledger(result, offered: int, cost: int) -> dict:
    """Checks of one open-loop answer against its own ring and the host's
    replay of the request stream, each a count of what is out of place.

    `offered` is the number of request records the stream offered before
    the answer's last tick (accepted candidates × batch) and `cost` the
    service ticks of one request.

      requests_unbalanced: injected + dropped differs from what was offered;
        requests done exceed requests injected; the ring's arrivals or
        completions differ in number from the counters.
      requests_not_once: a request completed twice, or completed without
        having been injected, or injected twice.
      sojourn_misses: a completion whose sojourn is not its pop tick minus
        its inject tick plus its cost, whose inject tick is not its
        arrival's; the counters' sojourn sum, or any of the reported
        percentiles, differing from the ring's.
    """
    ev = result.trace.events
    kind = ev[:, rtr.LANE_KIND]
    arr = ev[kind == rtr.EV_ARRIVAL]
    soj = ev[kind == rtr.EV_SOJOURN]
    inj, drop = int(result.arrivals_injected), int(result.arrivals_dropped)
    done = int(result.requests_done)
    unbalanced = (int(inj + drop != offered) + int(done > inj)
                  + int(len(arr) != inj) + int(len(soj) != done))

    arr_ids = arr[:, rtr.LANE_HOPS]
    soj_ids = soj[:, rtr.LANE_HOPS]
    ids, counts = np.unique(arr_ids, return_counts=True)
    not_once = int(np.sum(counts - 1))
    sids, scounts = np.unique(soj_ids, return_counts=True)
    not_once += int(np.sum(scounts - 1))
    not_once += int(np.count_nonzero(~np.isin(sids, ids)))

    rtt = soj[:, rtr.LANE_RTT].astype(np.int64)
    pop_t = soj[:, rtr.LANE_TICK].astype(np.int64)
    inj_t = soj[:, rtr.LANE_VICTIM].astype(np.int64)
    misses = int(np.count_nonzero(rtt != pop_t - inj_t + cost))
    arr_tick = dict(zip(arr_ids.tolist(), arr[:, rtr.LANE_TICK].tolist()))
    misses += sum(arr_tick.get(i, -1) != t
                  for i, t in zip(soj_ids.tolist(), inj_t.tolist()))
    misses += int(int(rtt.sum()) != int(result.sojourn_sum_ticks))
    want = nearest_rank(rtt)
    got = result.sojourn
    if want is None or got is None:
        misses += int((want is None) != (got is None))
    else:
        misses += sum(got.get(k) != v for k, v in want.items())
    return {"requests_unbalanced": unbalanced,
            "requests_not_once": not_once, "sojourn_misses": misses}


def offered_requests(ar, seed: int, gap_q8: int, batch: int,
                     ticks: int) -> int:
    """Request records that the stream offers before tick `ticks`:
    accepted candidates × batch, from the reference's stream functions
    over every candidate at once (a gap is at least one tick, so `ticks`
    candidates always reach past the end)."""
    import jax.numpy as jnp

    from bench.reference import arrivals as rarr
    aseed = rarr.stream_seed(seed)
    k = jnp.arange(ticks + 1, dtype=jnp.int32)
    fire = np.cumsum(np.asarray(rarr.gap_ticks(aseed, k, gap_q8), np.int64))
    n = int(np.count_nonzero(fire < ticks))
    if n == 0:
        return 0
    ok = rarr.accepted(ar, aseed, k[:n], jnp.asarray(fire[:n], jnp.int32))
    return int(np.count_nonzero(np.asarray(ok))) * min(max(batch, 1),
                                                       rarr.ARRIVAL_K)
