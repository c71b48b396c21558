#!/usr/bin/env bash
# Static-analysis gate (run by hand; tests/test_analysis.py holds the
# same rules and the clean repo in tier 1): the engine-invariant linter
# (presto_tpu/analysis/) in clean mode, PLUS a seeded-violation
# self-test proving the gate can actually fail — a lint gate that
# can't detect its own fixture violations is green paint.
#
#   1. `python -m presto_tpu.analysis` over the repo must exit 0
#      (every finding fixed, suppressed-with-reason, or baselined
#      with a reviewed justification).
#   2. Each of the four rule families must flag a seeded known-bad
#      fixture (one per family) written to a temp dir; a family that
#      goes silent fails the gate.
set -o pipefail
cd "$(dirname "$0")/.."

python -m presto_tpu.analysis "$@" || exit $?

python - <<'PY' || exit $?
import sys
import tempfile
from pathlib import Path

from presto_tpu.analysis import analyze

SEEDS = {
    "PT101": (
        "trace_mod.py",
        "import jax\n\n\n"
        "def _make_step():\n"
        "    def step(batch):\n"
        "        return int(batch['n'])\n"
        "    return jax.jit(step)\n"),
    "PT201": (
        "cache_mod.py",
        "import os\n\n"
        "from presto_tpu.cache.exec_cache import EXEC_CACHE\n\n\n"
        "def build():\n"
        "    def builder():\n"
        "        f = os.environ.get('PRESTO_TPU_SEEDED', '0')\n"
        "        return lambda b: b if f == '1' else -b\n"
        "    return EXEC_CACHE.get_or_build(\n"
        "        EXEC_CACHE.key_of('unrelated_tag', 1), builder)\n"),
    "PT301": (
        "lock_mod.py",
        "import threading\n\n\n"
        "class Shared:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._items = []\n\n"
        "    def add(self, x):\n"
        "        with self._lock:\n"
        "            self._items.append(x)\n\n"
        "    def drop(self, x):\n"
        "        self._items.remove(x)\n"),
    "PT401": (
        "test_env_mod.py",
        "import os\n\n\n"
        "def test_seeded():\n"
        "    os.environ['PRESTO_TPU_SEEDED'] = '1'\n"),
}

with tempfile.TemporaryDirectory() as td:
    root = Path(td)
    for rule, (name, src) in SEEDS.items():
        (root / name).write_text(src)
    res = analyze([td], root=td, baseline=[])
    found = {f.rule for f in res.findings}
    missing = sorted(set(SEEDS) - found)
    if missing:
        print("lint gate self-test FAILED: rule families went silent "
              f"on their seeded violations: {missing}", file=sys.stderr)
        sys.exit(1)
    print("lint gate: repo clean, all %d seeded rule families flagged "
          "(%s)" % (len(SEEDS), ", ".join(sorted(SEEDS))))
PY
