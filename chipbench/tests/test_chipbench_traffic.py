"""Traffic generation and the pool sizing rule of the benchmark's mixes."""
import json
from collections import Counter
from pathlib import Path

import pytest

from chipbench import traffic

BENCH = Path(__file__).resolve().parents[1]


def mix(name):
    return traffic.load_mix(BENCH / "traffic" / f"{name}.json")


@pytest.mark.parametrize("name,pages,per_seq", [
    ("longprompt_8s", 1088, 134), ("longprompt_16s", 2176, 134), ("shortprompt_16s", 512, 32)])
def test_pool_sizing_rule(name, pages, per_seq):
    # sessions x pages of the longest turn, rounded up to whole 64-page arenas
    assert traffic.pool_sizing(mix(name)) == (pages, per_seq)


def test_deck_keeps_weights_and_every_value():
    assert Counter(traffic.deck([64, 128, 256], [0.5, 0.3, 0.2], 16)) == {64: 8, 128: 5, 256: 3}
    assert Counter(traffic.deck([1024, 2048], [1, 1], 8)) == {1024: 4, 2048: 4}
    assert Counter(traffic.deck([1, 2, 3], [100, 1, 1], 3)) == {1: 1, 2: 1, 3: 1}


@pytest.mark.parametrize("name", ["longprompt_8s", "longprompt_16s", "shortprompt_16s"])
def test_first_round_holds_every_prompt_length(name):
    m = mix(name)
    s = traffic.Sessions(m, seed=2**31 + 17, vocab=1000)
    lens = {len(s.next_turn(i)[0]) for i in range(m["sessions"])}
    assert lens == set(m["prompt_tokens"]["values"])


def test_seeds_share_the_work_in_another_order():
    m = mix("shortprompt_16s")
    rounds = {}
    for seed in (1, 2**33 + 5):
        s = traffic.Sessions(m, seed, vocab=1000)
        rounds[seed] = [[(len(p), a) for p, a in (s.next_turn(i) for i in range(16))]
                        for _ in range(3)]
    a, b = rounds.values()
    assert [Counter(r) for r in a] == [Counter(r) for r in b]
    assert a != b


def test_same_seed_same_turns():
    m = mix("longprompt_8s")
    one, two = (traffic.Sessions(m, 99, vocab=50) for _ in range(2))
    assert [one.next_turn(3) for _ in range(2)] == [two.next_turn(3) for _ in range(2)]
    assert all(0 <= t < 50 for t in one.next_turn(0)[0])


def test_open_loop_mix_is_refused(tmp_path):
    p = tmp_path / "open.json"
    p.write_text(json.dumps({**mix("longprompt_8s"), "loop": "open"}))
    with pytest.raises(ValueError):
        traffic.load_mix(p)
