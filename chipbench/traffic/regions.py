"""Regional camera fleet with correlated drift, replayed from pools.

The benchmark's own copy of the synthetic fleet of `make_fleet` and
`DomainBank`: cameras sit in regions; a region follows a schedule of
domains; a domain is a bigram language over the vocabulary whose
next-token law is softmax(E_d E_d^T / (tau sqrt(dim))) with
self-transitions removed and a uniform first token. Each camera lags
its region by a seeded offset, and only the regions named in
`drifting_regions` switch domain, at `switch_time + region_stagger * r`
for region r, to the domain whose token-bucket histogram lies farthest
(Jensen-Shannon) from the one they leave, so every seed drifts.

Set-up draws `pool_rows` sequences of `pool_len` tokens per domain on
the device, in one jitted call from the seed, and `draws_per_camera`
start rows for each camera on the host. In the timed window a camera's
`sample(t, batch, seq_len)` returns `batch` consecutive pool rows from
its next start (in a cycle) as a read-only view: no bigram table, no
random generator and no copy is made there.

Parameters (the traffic file's keys): regions, cameras_per_region,
drifting_regions, switch_time, region_stagger, num_domains, domain_dim,
tau, pool_rows, pool_len, draws_per_camera, lag_max, region_spread,
region_distance, sig_buckets.
"""
from __future__ import annotations

import functools
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _pools(key, vocab: int, domains: int, dim: int, rows: int, length: int,
           tau: float = 0.15):
    """(domains, rows, length) int32 token pools."""
    def one(k):
        ke, k0, ks = jax.random.split(k, 3)
        E = jax.random.normal(ke, (vocab, dim), jnp.float32)
        scale = 1.0 / (tau * np.sqrt(dim))

        def step(tok, kk):
            lg = (E[tok] @ E.T) * scale
            lg = jnp.where(jnp.arange(vocab)[None] == tok[:, None],
                           -jnp.inf, lg)
            nxt = jax.random.categorical(kk, lg, axis=-1).astype(jnp.int32)
            return nxt, tok

        first = jax.random.randint(k0, (rows,), 0, vocab, jnp.int32)
        _, seq = jax.lax.scan(step, first, jax.random.split(ks, length))
        return seq.T
    return jax.lax.map(one, jax.random.split(key, domains))


class Clock:
    """Seconds the cameras spent answering `sample`: the load
    generator's own share of a window."""

    def __init__(self):
        self.seconds = 0.0


class ReplayStream:
    """The controller's view of one camera: `stream_id`, `loc` and
    `sample(t, batch, seq_len)`, which returns `batch` consecutive rows
    of the camera's current domain pool (read-only views; pool rows are
    independent draws) from the next start drawn for it in set-up."""

    def __init__(self, stream_id: str, loc, pools: np.ndarray, first: int,
                 after: int, switch: float, lag: float, starts: np.ndarray,
                 clock: Clock):
        self.stream_id = stream_id
        self.loc = tuple(loc)
        self.pools = pools
        self.first, self.after, self.switch = first, after, switch
        self.lag = lag
        self.starts = starts
        self.clock = clock
        self._next = 0

    def domain_at(self, t: float) -> int:
        return self.after if t - self.lag >= self.switch else self.first

    def sample(self, t: float, batch: int, seq_len: int) -> np.ndarray:
        t0 = time.perf_counter()
        k = self._next
        self._next = (k + 1) % len(self.starts)
        lo = min(self.starts[k], self.pools.shape[1] - batch)
        out = self.pools[self.domain_at(t), lo:lo + batch, :seq_len]
        self.clock.seconds += time.perf_counter() - t0
        if out.shape != (batch, seq_len):
            raise ValueError(f"({batch}, {seq_len}) exceeds the pool "
                             f"{self.pools.shape[1:]}")
        return out


def _bucket_js(pools: np.ndarray, buckets: int, vocab: int) -> np.ndarray:
    """(D, D) Jensen-Shannon divergence between the domains' pooled
    token-bucket histograms."""
    idx = np.clip(pools.reshape(len(pools), -1).astype(np.int64)
                  * buckets // vocab, 0, buckets - 1)
    h = np.stack([np.bincount(i, minlength=buckets) for i in idx]) + 1e-12
    h = h / h.sum(-1, keepdims=True)
    m = 0.5 * (h[:, None] + h[None])
    kl = lambda a, b: np.sum(a * np.log(a / b), -1)
    return 0.5 * kl(h[:, None], m) + 0.5 * kl(h[None], m)


def build(params: dict, *, vocab: int, seed: int, key) -> List[ReplayStream]:
    """The fleet's cameras, in region order. `key` is the device key made
    from the same seed; `seed` seeds the host generators."""
    p = params
    pools = np.asarray(_pools(key, vocab, p["num_domains"], p["domain_dim"],
                              p["pool_rows"], p["pool_len"], p["tau"]))
    dist = _bucket_js(pools, p["sig_buckets"], vocab)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 1])
    drifting = set(p["drifting_regions"])
    n_cams = p["regions"] * p["cameras_per_region"]
    starts = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 2]).integers(
        0, p["pool_rows"], size=(n_cams, p["draws_per_camera"]),
        dtype=np.int32)
    starts = [memoryview(row) for row in starts]   # quick to index
    pools.setflags(write=False)
    clock = Clock()
    streams = []
    for r in range(p["regions"]):
        dom = int(rng.integers(0, p["num_domains"]))
        far = int(np.argmax(dist[dom]))
        ts = (p["switch_time"] + p["region_stagger"] * r if r in drifting
              else float("inf"))
        cx = r * p["region_distance"]
        for c in range(p["cameras_per_region"]):
            loc = (cx + rng.uniform(-p["region_spread"], p["region_spread"]),
                   rng.uniform(-p["region_spread"], p["region_spread"]))
            lag = float(rng.uniform(0.0, p["lag_max"]))
            streams.append(ReplayStream(
                f"cam{r}_{c}", loc, pools, dom, far, ts, lag,
                starts[len(streams)], clock))
    return streams
