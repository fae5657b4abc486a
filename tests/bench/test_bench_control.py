"""The control of ``correct``: the plain reference computed one precision
step below the configuration's (float8 e4m3 in place of bfloat16), read
at every position of the same tokens and in its layer-0 keys and values,
against the reference's own bfloat16 picks and keys and values, and
judged by the decode cell's own limits through the same ``check.verdict``
as a run.  At the served widths and vocabulary, cut to a depth and length
a test run can hold: the bfloat16 picks lie on the reference's best, and
the control fails the cell's check."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

CONFIG = dict(json.loads((ROOT / "bench" / "configs"
                          / "qwen1.5-0.5b-hnn-spike.json").read_text()),
              num_hidden_layers=2)
LIMITS = json.loads((ROOT / "bench" / "traffic"
                     / "qwen05b-spike-decode.json").read_text())["check"][
    "limits"]
SEQ = 128


@pytest.fixture(scope="module")
def ref():
    from bench.reference import Reference
    return Reference(CONFIG, SEQ)


def _readings(ref, seed, mode):
    """The gaps of ``mode``'s own picks at every position after a prompt
    of half the length, judged by the bfloat16 reference, under the
    names a run's readings carry."""
    from bench import check
    from bench.weights import split_seed
    rng = np.random.default_rng(seed)
    half = SEQ // 2
    prompt = rng.integers(0, CONFIG["vocab_size"], half).tolist()
    served = rng.integers(0, CONFIG["vocab_size"], half).tolist()
    gap, _ = ref.gaps(split_seed(seed), prompt, served, control=mode)
    seq = prompt + served[:-1]
    want = ref.kv0(split_seed(seed), seq, half)
    got = ref.kv0(split_seed(seed), seq, half, mode)
    return {"max_logit_gap": float(gap.max()),
            "mean_logit_gap": float(gap.mean()),
            "kv_rel_err": max(check._rel_err(a, b) for a, b in zip(got, want))}


@pytest.mark.parametrize("seed", [2 ** 31 + 77, 5])
def test_float8_control_departs_from_the_reference(ref, seed):
    from bench import check
    sound = _readings(ref, seed, "bf16")
    control = _readings(ref, seed, "fp8")
    assert sound["max_logit_gap"] < 0.05 and sound["mean_logit_gap"] < 1e-3
    assert sound["kv_rel_err"] == 0
    assert check.passed(check.verdict(sound, LIMITS))
    assert not check.passed(check.verdict(control, LIMITS)), control
