"""The traffic generator: seeds, clip ranges, medians, due times."""
import json

import numpy as np
import pytest

from bench import generate
from bench.lookup import BENCH_DIR

# an open-loop mix with shared system prompts, as a later cell would bring
OPEN_LOOP = {
    "runner": "serve", "loop": "open", "rate_per_s": 2.0, "slots": 16,
    "page_size": 16, "max_context": 4096,
    "shared_prefix": {"tenants": 8, "length": 2048, "zipf": 1.1},
    "prompt": {"median": 192, "sigma": 0.6, "min": 32, "max": 768},
    "output": {"median": 64, "sigma": 0.6, "min": 16, "max": 256},
}
MIXES = sorted(p.stem for p in (BENCH_DIR / "traffic").glob("*.json")
               if json.loads(p.read_text())["runner"] == "serve") + ["open-loop"]


def spec(name):
    if name == "open-loop":
        return OPEN_LOOP
    return json.loads((BENCH_DIR / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_trace_other_seed_other_order(mix):
    s = spec(mix)
    a = generate.make_trace(s, 2**40 + 11, 100_352, 30)
    b = generate.make_trace(s, 2**40 + 11, 100_352, 30)
    c = generate.make_trace(s, 2**40 + 12, 100_352, 30)
    for x, y in ((a.prompt, b.prompt), (a.output, b.output), (a.tenant, b.tenant)):
        np.testing.assert_array_equal(x, y)
    np.testing.assert_array_equal(a.tokens(3, 50), b.tokens(3, 50))
    assert not np.array_equal(a.prompt, c.prompt)
    assert not np.array_equal(a.tokens(3, 50), c.tokens(3, 50))
    # the same multiset of sizes, in another order (outputs up to the
    # context cap, which depends on the prompt each is paired with)
    np.testing.assert_array_equal(np.sort(a.prompt), np.sort(c.prompt))
    assert abs(a.output.sum() - c.output.sum()) < 0.01 * a.output.sum()


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_respect_clip_ranges_and_medians(mix):
    s = spec(mix)
    t = generate.make_trace(s, 7, 100_352, 51)
    for arr, key in ((t.prompt, "prompt"), (t.output, "output")):
        lo, hi = s[key]["min"], s[key]["max"]
        assert arr.min() >= min(lo, 1) and arr.max() <= hi
    assert t.prompt.min() >= s["prompt"]["min"]
    # medians of the unclipped draws, within one stratum
    draws = generate.lognormal_strata(s["prompt"], 1001)
    assert np.median(draws) == pytest.approx(s["prompt"]["median"], rel=0.01)
    draws = generate.lognormal_strata(s["output"], 1001)
    assert np.median(draws) == pytest.approx(s["output"]["median"], rel=0.01)
    cap = s["max_context"] - (s["shared_prefix"]["length"] if "shared_prefix" in s else 0)
    assert (t.prompt + t.output <= cap).all()


def test_open_loop_schedule_stamps_due_times():
    s = spec("open-loop")
    t = generate.make_trace(s, 3, 100_352, 40)
    assert len(t) == round(s["rate_per_s"] * 40)
    assert (np.diff(t.due) >= 0).all() and 0 <= t.due[0] and t.due[-1] < 40
    # Zipf popularity: tenant 0 most requested, every tenant present
    counts = np.bincount(t.tenant, minlength=s["shared_prefix"]["tenants"])
    assert counts[0] == counts.max() and (counts > 0).all()


def test_ttft_is_timed_from_the_due_time():
    """A request due at 1.0 s whose first token comes at 1.5 s has a time
    to first token of 0.5 s, however late it was admitted."""
    from bench.runners import serve

    class Win:
        due = {7: 101.0, 8: 103.0}

    stamps = serve.Stamps({})
    stamps.times[7] = [101.5, 101.6, 101.8]
    stamps.times[8] = [103.2]
    e2e = serve._end_to_end(stamps, Win(), [7, 8], 100.0, 110.0, 10.0)
    assert e2e["decode_tok_s"] == pytest.approx(0.4)
    assert e2e["_ttft_n"] == 2
    assert e2e["ttft_p50_ms"] == pytest.approx(1e3 * np.percentile([0.5, 0.2], 50))
    assert e2e["itl_p95_ms"] == pytest.approx(1e3 * np.percentile([0.1, 0.2], 95))


def test_steady_start_is_part_way_and_length_biased():
    s = spec("decode-heavy")
    start = generate.steady_start(s, 5, s["clients"])
    assert len(start) == s["clients"]
    for p, done, left in start:
        assert left >= 1 and done >= 0 and p + done + left <= s["max_context"]
    outs = [d + left for _, d, left in start]
    assert np.median(outs) > s["output"]["median"]
