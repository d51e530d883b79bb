"""Drive a whole run with the timed path broken underneath and see the
comparison come out false; and true with nothing broken.

As tests: on the CPU (``--rehearse``, which skips only the look for a
chip), a short window.  On the chip, at the cell's own size and load:

    python3 benchmarks/tests/test_faults.py <fault> --workload <cell> --seed <n> --seconds <s>

which plants the fault and hands the rest to run.py: the result line has
to say ``"correct": false`` and the exit code has to be 1.

The faults these cells can have: an answer altered where it is produced, and
a step that returns its state unchanged.  (They take no mean over a batch
and exchange nothing between chips.)
"""

import importlib.util
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "base1-token-10k.open-small-uniform"


def run_py():
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(ROOT, "benchmarks", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def plant_altered_answer(setattr_):
    """One call in five answers remaining + 1.  Returns the call counter."""
    from gubernator_tpu.transport import fastwire

    real = fastwire.encode_resp
    calls = [0]

    def altered(mat):
        calls[0] += 1
        if calls[0] % 5 == 0:
            mat = mat.copy()
            mat[2, :] += 1
        return real(mat)

    setattr_(fastwire, "encode_resp", altered)
    return calls


def plant_state_unchanged(setattr_):
    """Every tick's new state is dropped.  Returns the tick counter."""
    import jax
    import jax.numpy as jnp

    from gubernator_tpu.ops.engine import TickEngine

    real = TickEngine.submit_columns
    ticks = [0]

    def unchanged(self, cols, now=None):
        ticks[0] += 1
        before = jax.tree.map(jnp.copy, self.state)
        handle = real(self, cols, now)
        self.state = before
        return handle

    setattr_(TickEngine, "submit_columns", unchanged)
    return ticks


FAULTS = {"altered_answer": plant_altered_answer,
          "state_unchanged": plant_state_unchanged}


def rehearse(capfd, seed):
    rc = run_py().main(["--workload", CELL, "--seed", str(seed), "--seconds", "3",
                        "--trace", "0", "--rehearse", "--rate", "300"])
    out = capfd.readouterr().out
    said = re.search(r"the comparison alone would say correct=(True|False)", out)
    assert rc == 1 and said, out[-2000:]
    assert '"correct": false' in out.splitlines()[-1]     # a rehearsal never passes
    return said.group(1) == "True", out


def test_sound_run_compares_equal(capfd):
    ok, out = rehearse(capfd, 101)
    assert ok, out[-2000:]


def test_an_altered_answer_is_seen(capfd, monkeypatch):
    calls = plant_altered_answer(monkeypatch.setattr)
    ok, out = rehearse(capfd, 102)
    assert calls[0] > 0 and not ok, out[-2000:]


def test_a_state_left_unchanged_is_seen(capfd, monkeypatch):
    ticks = plant_state_unchanged(monkeypatch.setattr)
    ok, out = rehearse(capfd, 103)
    assert ticks[0] > 0 and not ok, out[-2000:]


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    count = FAULTS[sys.argv[1]](setattr)
    rc = run_py().main(sys.argv[2:])
    print(f"fault {sys.argv[1]}: planted {count[0]} times; exit code {rc}", file=sys.stderr)
    sys.exit(rc)
