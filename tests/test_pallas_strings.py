"""Pallas string kernels vs the jnp reference kernels (differential:
same inputs, every pattern shape) — interpreter mode on the CPU mesh,
compiled on real TPU [SURVEY §4 fuzz-ish tier; config 5]."""

import numpy as np
import pytest

from presto_tpu.ops.pallas_strings import like_mask_pallas, starts_with_pallas
from presto_tpu.ops.strings import like_mask, starts_with_mask


def _rows(rng, n, width, vocab):
    """Random zero-padded byte rows composed from vocabulary words."""
    out = np.zeros((n, width), dtype=np.uint8)
    for i in range(n):
        s = b" ".join(rng.choice(vocab) for _ in range(rng.integers(1, 5)))[:width]
        out[i, : len(s)] = np.frombuffer(s, dtype=np.uint8)
    return out


VOCAB = [w.encode() for w in
         ["sky", "blue", "skyblue", "almond", "antique", "sly", "s", "bluesky"]]

PATTERNS = [
    "%sky%",            # contains
    "sky%",             # prefix
    "%blue",            # suffix
    "%sky%blue%",       # ordered segments
    "almond%antique",   # anchored both ends
    "%skyblue%",
    "sly",              # exact (no wildcard)
    "%zzz%",            # never matches
]


@pytest.fixture(scope="module")
def data(rng):
    return _rows(np.random.default_rng(11), 513, 44, VOCAB)


@pytest.mark.parametrize("pattern", PATTERNS)
def test_like_pallas_matches_reference(data, pattern):
    ref = np.asarray(like_mask(data, pattern))
    got = np.asarray(like_mask_pallas(data, pattern))
    np.testing.assert_array_equal(got, ref, err_msg=pattern)
    # sanity: the interesting patterns hit at least one row
    if pattern not in ("%zzz%", "almond%antique", "sly"):
        assert ref.any()


def test_like_edge_semantics(data):
    """Over-length literals never match; LIKE '' matches only empty
    rows; all-wildcard patterns match everything."""
    w = data.shape[1]
    long_lit = "x" * (w + 3)
    for fn in (like_mask, like_mask_pallas):
        assert not np.asarray(fn(data, long_lit)).any()
        empties = np.asarray(fn(data, ""))
        lens = (data != 0).sum(axis=1)
        np.testing.assert_array_equal(empties, lens == 0)
        assert np.asarray(fn(data, "%%")).all()


def test_like_suffix_with_repeats():
    """End-anchored segment occurring mid-string too (the '%1' bug)."""
    rows = [b"ab1cd1", b"ab1cd2", b"1", b"x1y", b""]
    data = np.zeros((5, 8), np.uint8)
    for i, r in enumerate(rows):
        data[i, : len(r)] = np.frombuffer(r, np.uint8)
    want = [r.endswith(b"1") for r in rows]
    for fn in (like_mask, like_mask_pallas):
        np.testing.assert_array_equal(np.asarray(fn(data, "%1")), want)


def test_use_pallas_env_values(monkeypatch):
    from presto_tpu.ops.strings import use_pallas

    for v in ("0", "false", "False", "off", "no", ""):
        monkeypatch.setenv("PRESTO_TPU_PALLAS", v)
        assert not use_pallas(), v
    for v in ("1", "true", "on"):
        monkeypatch.setenv("PRESTO_TPU_PALLAS", v)
        assert use_pallas(), v


def test_starts_with_pallas_matches_reference(data):
    for prefix in ["sky", "al", "blue", "zz"]:
        ref = np.asarray(starts_with_mask(data, prefix))
        got = np.asarray(starts_with_pallas(data, prefix))
        np.testing.assert_array_equal(got, ref, err_msg=prefix)


def test_like_pallas_via_sql(env_pallas):
    """Force the Pallas route through the SQL engine and diff against
    the jnp route on a real TPC-H predicate (q9-shape p_name LIKE)."""
    session, tables = env_pallas
    q = "select count(*) as n from part where p_name like '%green%'"
    got = int(session.sql(q)["n"][0])
    want = int(tables["part"]["p_name"].str.contains("green").sum())
    assert got == want and got > 0


@pytest.fixture(scope="module")
def env_pallas(monkeypatch_module):
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.runtime.session import Session

    monkeypatch_module.setenv("PRESTO_TPU_PALLAS", "1")
    conn = TpchConnector(sf=0.005, units_per_split=1 << 14)
    session = Session({"tpch": conn})
    tables = {"part": conn.table_pandas("part")}
    return session, tables


@pytest.fixture(scope="module")
def monkeypatch_module():
    from _pytest.monkeypatch import MonkeyPatch

    mp = MonkeyPatch()
    yield mp
    mp.undo()


def test_empty_prefix_matches_everything():
    """starts_with('') is vacuously true; the kernel wrapper used to
    crash on an empty needle (round-1 advisor finding)."""
    import jax.numpy as jnp
    import numpy as np

    from presto_tpu.ops.pallas_strings import starts_with_pallas

    data = jnp.asarray(np.zeros((8, 12), np.uint8))
    out = np.asarray(starts_with_pallas(data, ""))
    assert out.all()


def test_kernel_compile_error_reaches_the_query(env_pallas, monkeypatch):
    """No probe stands between a chosen kernel and its caller: when the
    LIKE kernel's compile raises, the query FAILS with that error
    instead of quietly answering from the jnp twin."""
    import presto_tpu.ops.pallas_strings as ps

    def boom(data, pattern, interpret=None):
        raise RuntimeError("mosaic refused the program")

    monkeypatch.setattr(ps, "like_mask_pallas", boom)
    session, _tables = env_pallas
    # a literal no other test used: the step must be traced afresh
    q = "select count(*) as n from part where p_name like '%unswallowed%'"
    with pytest.raises(Exception, match="mosaic refused the program"):
        session.sql(q)
