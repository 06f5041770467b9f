"""Batch scheduling (paper Sec. 4, Fig. 7).

Similar consecutive batches make the optimizer take compounding steps in a
suboptimal direction → accuracy spikes. The paper measures batch similarity
via symmetrized KL divergence of training-label distributions and proposes:
 (i) a fixed order maximizing consecutive distance (max-TSP, solved with
     simulated annealing — paper App. B uses python-tsp's SA), and
 (ii) sampling the next batch weighted by distance to the current one.
"""
from __future__ import annotations

from typing import Callable, List, Sequence, Tuple

import numpy as np


def label_distributions(batch_labels: Sequence[np.ndarray], num_classes: int,
                        smooth: float = 1e-6) -> np.ndarray:
    """Normalized training-label distribution p_i = c_i / Σ_j c_j per batch."""
    out = np.zeros((len(batch_labels), num_classes), dtype=np.float64)
    for i, lab in enumerate(batch_labels):
        cnt = np.bincount(np.asarray(lab), minlength=num_classes).astype(np.float64)
        out[i] = cnt + smooth
        out[i] /= out[i].sum()
    return out


def pairwise_kl_distance(p: np.ndarray) -> np.ndarray:
    """Symmetrized KL: d_ab = KL(a‖b) + KL(b‖a). Returns (B, B)."""
    logp = np.log(p)
    # KL(a||b) = Σ p_a (log p_a − log p_b)
    ent = (p * logp).sum(axis=1)                       # Σ p_a log p_a
    cross = p @ logp.T                                 # Σ p_a log p_b
    kl = ent[:, None] - cross
    return kl + kl.T


# Generator.random() is the top 53 bits of a 64-bit word times 2**-53
_U53 = 2.0 ** -53
_RAW_BLOCK = 4096


def _raw_draws(rng: np.random.Generator
               ) -> Tuple[Callable[[int], Tuple[int, int]],
                          Callable[[], float], Callable[[], None]]:
    """`(pair_below, random, sync)`: `Generator.integers(0, n, size=2)`
    (for n ≥ 2) and `Generator.random()` of `rng`, rebuilt from raw 64-bit words that are
    drawn in bulk, value for value and draw for draw.

    As in NumPy's C code, a bounded int is a 32-bit draw through the bit
    generator's own 32-bit buffer (the low half of a word first, the high
    half kept for the next 32-bit draw, across calls), mapped by Lemire's
    method with its rejection loop; a double is ``(word >> 11) * 2**-53``
    and leaves the buffer alone. That holds for the bit generators with
    such a buffer (PCG64, PCG64DXSM, Philox, SFC64); others raise.
    `sync()` then leaves the bit generator where the same `Generator` calls
    would have left it.
    """
    bg = rng.bit_generator
    start = bg.state
    if "has_uint32" not in start:
        raise ValueError(
            f"tsp_max_order needs a bit generator with a 32-bit buffer "
            f"(PCG64, PCG64DXSM, Philox, SFC64), not "
            f"{start.get('bit_generator')!r}")
    has32, buf = bool(start["has_uint32"]), int(start["uinteger"])
    words: List[int] = []
    pos, drawn = _RAW_BLOCK, 0          # words[pos] is the next unused word

    def refill() -> None:
        nonlocal words, pos, drawn
        words = bg.random_raw(_RAW_BLOCK).tolist()
        pos, drawn = 0, drawn + _RAW_BLOCK

    def pair_below(n: int) -> Tuple[int, int]:
        nonlocal has32, buf, pos
        threshold = (2 ** 32 - n) % n
        first = -1
        while True:
            if has32:
                has32 = False
                x = buf
            else:
                if pos == _RAW_BLOCK:
                    refill()
                w = words[pos]
                pos += 1
                has32, buf, x = True, w >> 32, w & 0xFFFFFFFF
            m = x * n
            if (m & 0xFFFFFFFF) < threshold:
                continue                # Lemire's rejection: draw again
            if first < 0:
                first = m >> 32
            else:
                return first, m >> 32

    def random() -> float:
        nonlocal pos
        if pos == _RAW_BLOCK:
            refill()
        pos += 1
        return (words[pos - 1] >> 11) * _U53

    def sync() -> None:
        bg.state = start
        bg.random_raw(drawn - (_RAW_BLOCK - pos))
        state = bg.state
        state["has_uint32"], state["uinteger"] = int(has32), buf
        bg.state = state

    return pair_below, random, sync


def _tour_len(rows: List[List[float]], o: List[int]) -> float:
    """`dist[o, np.roll(o, -1)].sum()` bit for bit, with `rows =
    dist.tolist()`: NumPy's float64 pairwise summation from 0.0 —
    sequential under 8 terms, else eight accumulators over blocks of 8
    combined as a tree and then the remainder in order."""
    t = [rows[a][b] for a, b in zip(o, o[1:] + o[:1])]
    n = len(t)
    if n > 128:     # NumPy halves recursively from here: let it
        return float(np.add.reduce(np.array(t)))
    if n < 8:
        s = 0.0
        for x in t:
            s += x
        return s
    r0, r1, r2, r3, r4, r5, r6, r7 = t[:8]
    m = n - n % 8
    for k in range(8, m, 8):
        r0 += t[k]; r1 += t[k + 1]; r2 += t[k + 2]; r3 += t[k + 3]
        r4 += t[k + 4]; r5 += t[k + 5]; r6 += t[k + 6]; r7 += t[k + 7]
    s = ((r0 + r1) + (r2 + r3)) + ((r4 + r5) + (r6 + r7))
    for x in t[m:]:
        s += x
    return 0.0 + s


def tsp_max_order(dist: np.ndarray, iters: int = 20_000, seed: int = 0,
                  t0: float = 1.0, t1: float = 1e-3) -> np.ndarray:
    """Max-distance closed tour via simulated annealing (2-opt + swap moves).

    Maximizing total consecutive distance ≡ solving max-TSP on the loop that
    visits every batch (paper: 'traveling salesman problem for finding the
    maximum distance loop').

    Each iteration draws two positions and a move (2-opt segment reversal
    or swap); a candidate no longer than the current tour is accepted with
    probability exp(Δ / T), T falling geometrically from `t0` to `t1`. The
    loop runs on Python lists and floats, with NumPy's own random values
    (`_raw_draws`) and tour sums (`_tour_len`), so the tour is the one the
    same annealing written with NumPy calls returns (DESIGN.md §2).
    """
    n = dist.shape[0]
    if n <= 2:
        return np.arange(n)
    if dist.dtype != np.float64:
        raise TypeError(f"tsp_max_order sums float64 distances as NumPy "
                        f"does; got {dist.dtype}")
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n)
    pair_below, random, sync = _raw_draws(rng)
    rows = dist.tolist()
    exp = np.exp
    order = perm.tolist()
    cur = _tour_len(rows, order)
    best, best_len = order, cur
    for it in range(iters):
        i, j = pair_below(n)
        if i == j:
            continue
        if i > j:
            i, j = j, i
        cand = order.copy()
        if random() < 0.5:
            cand[i:j + 1] = cand[i:j + 1][::-1]     # 2-opt segment reversal
        else:
            cand[i], cand[j] = cand[j], cand[i]     # swap
        new = _tour_len(rows, cand)
        # MAXIMIZE: accept if longer, or with SA probability (np.exp, not
        # math.exp: the two may differ in the last bit)
        if not new > cur:
            temp = t0 * (t1 / t0) ** (it / max(iters - 1, 1))
            if not random() < exp((new - cur) / max(temp, 1e-9)):
                continue
        order, cur = cand, new
        if cur > best_len:
            best, best_len = order, cur
    sync()
    return np.array(best, dtype=perm.dtype)


def weighted_sampling_order(dist: np.ndarray, num_epochs: int = 1,
                            seed: int = 0) -> np.ndarray:
    """Sample the next batch ∝ distance to the current batch, without
    replacement within an epoch (every batch used exactly once per epoch,
    keeping training unbiased — paper Sec. 4)."""
    n = dist.shape[0]
    rng = np.random.default_rng(seed)
    orders = []
    cur = int(rng.integers(n))
    for _ in range(num_epochs):
        remaining = set(range(n))
        epoch = []
        for _ in range(n):
            rem = np.array(sorted(remaining))
            w = dist[cur, rem].astype(np.float64)
            w = np.maximum(w, 1e-12)
            cur = int(rng.choice(rem, p=w / w.sum()))
            remaining.discard(cur)
            epoch.append(cur)
        orders.append(np.array(epoch))
    return np.concatenate(orders) if num_epochs > 1 else orders[0]


def make_schedule(
    batch_labels: Sequence[np.ndarray],
    num_classes: int,
    mode: str = "tsp",
    num_epochs: int = 1,
    seed: int = 0,
) -> np.ndarray:
    """Return the batch visit order for `num_epochs` epochs, flattened."""
    n = len(batch_labels)
    if mode == "none" or n <= 2:
        return np.tile(np.arange(n), num_epochs)
    p = label_distributions(batch_labels, num_classes)
    d = pairwise_kl_distance(p)
    if mode == "tsp":
        order = tsp_max_order(d, seed=seed)
        return np.tile(order, num_epochs)
    if mode == "weighted":
        return weighted_sampling_order(d, num_epochs=num_epochs, seed=seed)
    raise ValueError(f"unknown schedule mode: {mode}")
