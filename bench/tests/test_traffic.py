"""The generator: every seed runs the same requests in the same order;
the seed draws only the token ids."""
import json
import os

import pytest

from conftest import BENCH, DUMMY_TRAFFIC
from harness import traffic


def mix(name):
    return traffic.load_traffic(os.path.join(BENCH, "traffic", name + ".json"))


@pytest.mark.parametrize("t", [mix("longdecode"), DUMMY_TRAFFIC["dummy_serve"]],
                         ids=["longdecode", "poisson"])
def test_seed_draws_only_token_ids(t):
    shape = None
    for seed in (1, 2 ** 33 + 7, 3100000121):
        reqs = traffic.requests(t, seed, 51.0, 1000)
        s = [(r.rid, len(r.prompt), r.max_tokens, r.arrival_s) for r in reqs]
        assert shape is None or s == shape
        shape = s
    a = traffic.requests(t, 1, 51.0, 1000)[0].prompt
    b = traffic.requests(t, 2, 51.0, 1000)[0].prompt
    assert a != b and a == traffic.requests(t, 1, 51.0, 1000)[0].prompt
    assert {n for _, n, _, _ in shape} <= set(traffic.corpus(t))


def test_kind_names_a_harness_module(tmp_path):
    p = tmp_path / "mix.json"
    p.write_text(json.dumps({"kind": "nosuchkind"}))
    with pytest.raises(ValueError):
        traffic.load_traffic(str(p))
    assert mix("graph_b8s256")["kind"] == "graph"
