"""The one generator of open-loop serving traffic, from a mix's parameters.

Every seed gets the same work in another order, so that runs with
different seeds differ by noise and not by load: the same number of
requests, the same multiset of inter-arrival gaps (the quantiles of the
exponential distribution of a Poisson process at the mix's rate), the same
count of each request kind and of each tenant (tenants by Zipf popularity),
shuffled by the seed.  Only the indices a request asks about are drawn
afresh, from the same power law as the data (``floor(u^(1 + skew) * dim)``)
so that queries land where the data is.
"""
from __future__ import annotations

import dataclasses

import numpy as np

KINDS = ("top_k", "values_at")


@dataclasses.dataclass
class Schedule:
    due: np.ndarray       # (n,) seconds after the window opens
    kind: np.ndarray      # (n,) index into KINDS
    tenant: np.ndarray    # (n,) tenant index
    users: np.ndarray     # (n,) user index (top_k requests)
    coords: np.ndarray    # (n, coords_per_values_at, order) (values_at)

    def __len__(self) -> int:
        return int(self.due.shape[0])


def exact_counts(n: int, shares) -> np.ndarray:
    """``n`` split by ``shares`` with the largest remainders rounded up."""
    shares = np.asarray(shares, dtype=np.float64)
    raw = n * shares / shares.sum()
    counts = np.floor(raw).astype(np.int64)
    counts[np.argsort(counts - raw)[:n - counts.sum()]] += 1
    return counts


def zipf_shares(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1) ** s
    return w / w.sum()


def power_law(rng, dim: int, skew: float, size) -> np.ndarray:
    u = rng.uniform(1e-6, 1.0, size)
    return np.minimum((u ** (1.0 + skew) * dim).astype(np.int32), dim - 1)


def open_loop(mix: dict, dims, seed: int, seconds: float,
              rate: float | None = None) -> Schedule:
    rate = float(mix["rate_per_s"] if rate is None else rate)
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng([int(seed), 7])
    # the exponential distribution's n quantiles, scaled so that the last
    # request falls half a mean gap before the window closes
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = rng.permutation(gaps * seconds * (1 - 0.5 / n) / gaps.sum())
    due = np.cumsum(gaps)
    kind = rng.permutation(np.repeat(
        np.arange(len(KINDS)),
        exact_counts(n, [mix["mix"][k] for k in KINDS])))
    tenant = rng.permutation(np.repeat(
        np.arange(mix["tenants"]),
        exact_counts(n, zipf_shares(mix["tenants"], mix["tenant_zipf_s"]))))
    skew = float(mix["index_skew"])
    users = power_law(rng, dims[0], skew, n)
    coords = np.stack([power_law(rng, d, skew,
                                 (n, mix["coords_per_values_at"]))
                       for d in dims], axis=-1)
    return Schedule(due=due, kind=kind, tenant=tenant, users=users,
                    coords=coords)
