"""The generators: the same seed gives the same inputs, and every seed
gives the same amount of work in another order."""

import json

import numpy as np

from chipbench.common import BENCH_DIR
from chipbench.gen import poisson_lognormal, token_stream

BIG = 2 ** 31 + 977


def _traffic(name):
    return json.loads((BENCH_DIR / "traffic" / f"{name}.json").read_text())


def test_arrivals_same_work_for_every_seed():
    tr = _traffic("chat")
    a = poisson_lognormal.arrivals(tr, BIG, 30.0, 1000)
    b = poisson_lognormal.arrivals(tr, BIG + 1, 30.0, 1000)
    n = poisson_lognormal.requests_in_window(tr, 30.0)
    assert len(a) == len(b) == n
    assert all(0 < x.due < 30.0 for x in a) and [x.due for x in a] == sorted(x.due for x in a)
    assert [(x.due, len(x.prompt), x.max_new) for x in a] == \
        [(x.due, len(x.prompt), x.max_new) for x in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    other = poisson_lognormal.arrivals(dict(tr, schedule_seed=1), BIG, 30.0, 1000)
    assert [len(x.prompt) for x in other] != [len(x.prompt) for x in a]
    assert sorted(len(x.prompt) for x in other) == sorted(len(x.prompt) for x in a)
    lo, hi = tr["prompt_len"]["min"], tr["prompt_len"]["max"]
    assert all(lo <= len(x.prompt) <= hi for x in a)
    assert all(len(x.prompt) + x.max_new <= tr["max_len"] for x in a)
    again = poisson_lognormal.arrivals(tr, BIG, 30.0, 1000)
    assert all(np.array_equal(x.prompt, y.prompt) and x.due == y.due for x, y in zip(a, again))


def test_token_batches():
    tr = dict(_traffic("train_late"), seq_len=64)
    a = token_stream.batches(tr, BIG, 5)
    b = token_stream.batches(tr, BIG, 2)
    assert all(np.array_equal(x["inputs"], y["inputs"]) for x, y in zip(a, b))
    assert a[0]["inputs"].shape == (tr["n_workers"] * tr["rows_per_worker"], 64)
    assert np.array_equal(a[0]["inputs"][:, 1:], a[0]["labels"][:, :-1])
    rows = np.concatenate([x["inputs"] for x in a])
    assert len({r.tobytes() for r in rows}) == len(rows)
    assert rows.max() < tr["stream"]["vocab"]
    early = token_stream.batches(dict(_traffic("train_early"), seq_len=64), BIG, 1)[0]
    assert early["inputs"].shape[0] == 8
