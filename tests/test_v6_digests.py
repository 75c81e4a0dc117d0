"""The v6 talker map's vectorised fill (``pack.V6DigestFill``).

The map renders v6 talkers: fold digest -> the first 128-bit source seen
with it, in first-seen order, capped at ``pack.V6_DIGEST_CAP``.  The fill
must leave exactly what the per-row loop below leaves, kept here as the
reference, batch by batch: same entries, same insertion order.
"""

import numpy as np
import pytest

from ruleset_analysis_tpu.hostside import pack


def per_row_fill(dig: dict[int, int], rows6: np.ndarray) -> None:
    """The reference: one ``setdefault`` per v6 row, in stream order."""
    for r in rows6:
        if len(dig) >= pack.V6_DIGEST_CAP:
            break
        src = pack.limbs_u128(*r[pack.T6_SRC:pack.T6_SRC + 4])
        dig.setdefault(pack.fold_src32_host(src), src)


def rows_of(srcs) -> np.ndarray:
    """``[n, TUPLE6_COLS]`` v6 rows with the given ``[n, 4]`` source limbs."""
    srcs = np.asarray(srcs, dtype=np.uint32).reshape(-1, 4)
    rows = np.zeros((srcs.shape[0], pack.TUPLE6_COLS), dtype=np.uint32)
    rows[:, pack.T6_SRC:pack.T6_SRC + 4] = srcs
    rows[:, pack.T6_VALID] = 1
    return rows


def random_srcs(rng, n: int) -> np.ndarray:
    return rng.integers(0, 1 << 32, size=(n, 4), dtype=np.uint64).astype(np.uint32)


def bursty(rng, pool: np.ndarray, n: int) -> np.ndarray:
    """``n`` rows drawn from ``pool``, each repeated in a run of 1 to 5."""
    out = np.repeat(pool[rng.integers(0, len(pool), size=n)],
                    rng.integers(1, 6, size=n), axis=0)
    return out[:n]


def fold_twin(src) -> np.ndarray:
    """Another address with ``src``'s fold: l0 and l1 kept, l2 changed, and
    l3 solved so the state before the last multiply is the same."""
    m = 0xFFFFFFFF
    l0, l1, l2, l3 = (int(v) for v in src)
    h1 = ((((l0 * 0x9E3779B1) & m) ^ l1) * 0x85EBCA77) & m
    l2b = l2 ^ 0x5A5A5A5A
    h2, h2b = ((h1 ^ l2) * 0xC2B2AE3D) & m, ((h1 ^ l2b) * 0xC2B2AE3D) & m
    return np.array([l0, l1, l2b, l3 ^ h2 ^ h2b], dtype=np.uint32)


def _batches(case: str) -> list[np.ndarray]:
    rng = np.random.default_rng(2027)
    pool = random_srcs(rng, 300)
    if case == "runs_of_repeats":
        return [rows_of(bursty(rng, pool, 2000)) for _ in range(3)]
    if case == "first_seen_in_later_batch":
        late = random_srcs(rng, 40)
        return [rows_of(bursty(rng, pool, 500)),
                rows_of(np.concatenate([bursty(rng, pool, 200), late, pool[:50]])),
                rows_of(np.concatenate([late[::-1], random_srcs(rng, 7)]))]
    if case == "shared_fold_first_seen_wins":
        a, b = pool[5], fold_twin(pool[5])
        c, d = pool[9], fold_twin(pool[9])
        # one twin first seen in the same batch, the other in a later one
        return [rows_of([pool[1], b, b, pool[2], a, c]),
                rows_of([d, c, pool[3], d])]
    if case == "shared_fold_in_later_batches":
        # every known source's twin arrives batches later: none may enter
        return [rows_of(bursty(rng, pool[k::3], 400)) for k in range(3)] + [
            rows_of([fold_twin(p) for p in pool[::-1]]),
            rows_of(bursty(rng, pool, 400)),
        ]
    if case == "empty_batch":
        return [rows_of(np.zeros((0, 4))), rows_of(bursty(rng, pool, 400)),
                rows_of(np.zeros((0, 4))), rows_of(bursty(rng, pool, 400))]
    raise ValueError(case)


CASES = ["runs_of_repeats", "first_seen_in_later_batch",
         "shared_fold_first_seen_wins", "shared_fold_in_later_batches",
         "empty_batch"]


def _fill(fill: pack.V6DigestFill, rows6: np.ndarray, entry: str) -> None:
    if entry == "rows":
        fill.add_rows(rows6)
    else:  # the wire's [4, n] source limbs
        fill.add(np.ascontiguousarray(rows6[:, pack.T6_SRC:pack.T6_SRC + 4].T))


def assert_same_as_per_row(batches, entry: str) -> dict[int, int]:
    want: dict[int, int] = {}
    got: dict[int, int] = {}
    fill = pack.V6DigestFill(got)
    for rows6 in batches:
        per_row_fill(want, rows6)
        _fill(fill, rows6, entry)
        assert list(got.items()) == list(want.items())
    return got


@pytest.mark.parametrize("entry", ["rows", "limbs"])
@pytest.mark.parametrize("case", CASES)
def test_fill_equals_per_row_loop(case, entry):
    got = assert_same_as_per_row(_batches(case), entry)
    if case == "shared_fold_first_seen_wins":
        a, b = _batches(case)[0][[4, 1], pack.T6_SRC:pack.T6_SRC + 4]
        assert pack.fold_src32_np(a[:, None]) == pack.fold_src32_np(b[:, None])
        assert not np.array_equal(a, b)
        # b came first: it, not a, renders the shared digest
        assert pack.limbs_u128(*b) in got.values()
        assert pack.limbs_u128(*a) not in got.values()


@pytest.mark.parametrize("entry", ["rows", "limbs"])
def test_fill_stops_at_the_cap_mid_batch(monkeypatch, entry):
    monkeypatch.setattr(pack, "V6_DIGEST_CAP", 37)
    rng = np.random.default_rng(5)
    pool = random_srcs(rng, 200)
    batches = [rows_of(bursty(rng, pool[:20], 60)),  # under the cap
               rows_of(bursty(rng, pool, 300)),  # crosses it mid-batch
               rows_of(random_srcs(rng, 50))]  # past it: nothing enters
    got = assert_same_as_per_row(batches, entry)
    assert len(got) == 37


def test_fill_keeps_entries_put_in_beside_it():
    """A resume restores part of the map before the fill runs: the fill
    keeps those entries, even where a later row shares a restored digest
    with another address, and adds only what the map lacks."""
    rng = np.random.default_rng(11)
    pool = random_srcs(rng, 100)
    rows6 = rows_of(bursty(rng, pool, 600))
    want: dict[int, int] = {}
    per_row_fill(want, rows_of([fold_twin(p) for p in pool[40:60]]))
    got = dict(want)
    fill = pack.V6DigestFill(got)
    per_row_fill(want, rows6[:200])
    _fill(fill, rows6[:200], "rows")
    # restored after the first batch: an entry the fill did not make
    restored = fold_twin(pool[70])
    got[pack.fold_src32_host(pack.limbs_u128(*restored))] = pack.limbs_u128(*restored)
    want.setdefault(*list(got.items())[-1])
    late = rows_of(np.concatenate([rows6[200:, pack.T6_SRC:pack.T6_SRC + 4], pool[70:71]]))
    per_row_fill(want, late)
    _fill(fill, late, "rows")
    assert list(got.items()) == list(want.items())
