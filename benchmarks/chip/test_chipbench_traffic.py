"""The traffic generator: the same work for every seed, in another order."""
import numpy as np
import pytest

from chipbench import spec, traffic

CHAT = spec.load_cell("phi35moe.dcp.4c").traffic
SESS = spec.load_cell("minicpm3.longdecode.1c").traffic


@pytest.mark.parametrize("seed", [0, 2**31 + 12345, 2**40 + 1])
def test_every_seed_offers_the_same_work(seed):
    a, na = traffic.open_loop(CHAT, 1, 51)
    b, nb = traffic.open_loop(CHAT, seed, 51)
    assert na == nb and len(a) == len(b)
    for key in ("prompt_len", "max_new_tokens"):
        assert sorted(getattr(r, key) for r in a[:na]) == \
            sorted(getattr(r, key) for r in b[:nb])
    # the window's arrivals span the window whatever the order
    assert b[0].due == 0.0 and b[nb - 1].due <= 51.0
    assert b[nb].due >= 51.0 - 1e-9


def test_seed_changes_the_order_and_the_tokens():
    a, _ = traffic.open_loop(CHAT, 1, 51)
    b, _ = traffic.open_loop(CHAT, 2, 51)
    assert [r.prompt_len for r in a] != [r.prompt_len for r in b]
    ta = traffic.prompt_tokens(1, a[:3], 1000)
    tb = traffic.prompt_tokens(1, a[:3], 1000)
    assert all((x == y).all() for x, y in zip(ta, tb))


def test_chat_lengths_follow_table_1_on_the_ladder():
    reqs, n = traffic.open_loop(CHAT, 3, 51)
    lens = np.array([r.prompt_len for r in reqs[:n]])
    assert set(lens) <= set(CHAT["prompt"]["ladder"])
    # 95% Table 1 ShareGPT-4o (85.7% under 1k, 3.5% at 10k or more, the
    # top cut at 32256) and 5% of 32k-41k requests
    assert abs((lens < 1000).mean() - 0.95 * 0.857) < 0.03
    assert abs((lens >= 10000).mean() - (0.95 * 0.035 + 0.05)) < 0.02
    assert abs((lens > 32256).mean() - 0.05) < 0.01
    assert lens.max() <= 40960
    outs = [r.max_new_tokens for r in reqs]
    assert min(outs) >= 64 and max(outs) <= 512


def test_poisson_gaps_at_the_rate():
    reqs, n = traffic.open_loop(CHAT, 4, 51)
    assert n == round(CHAT["rate_per_s"] * 51)
    gaps = np.diff([r.due for r in reqs[:n]])
    assert np.std(gaps) == pytest.approx(np.mean(gaps), rel=0.3)


def test_sessions_fill_the_pool():
    k = traffic.session_count(SESS, 49152, 16, 16)
    lens = [r.prompt_len for r in traffic.sessions(SESS, 9, k)]
    need = sum(-(-(n + SESS["growth_tokens"]) // 16) * 16 for n in lens)
    assert need <= 49152
    more = traffic.prompt_lengths(SESS["prompt"], k + 1)
    assert sum(-(-(int(n) + SESS["growth_tokens"]) // 16) * 16
               for n in more) > 49152
    assert all(8000 <= n <= 15000 for n in lens)
    # the slot count caps a large pool
    assert traffic.session_count(SESS, 10**7, 16, 16) == 16


def test_mixed_prompt_components():
    spec_ = {"mix": [{"dataset": "sharegpt4o", "share": 0.95,
                      "cap": 32256},
                     {"dataset": "github_issue", "share": 0.05,
                      "cap": 200000}]}
    lens = traffic.prompt_lengths(spec_, 100)
    assert (lens >= 100000).sum() == 5
    assert lens.max() <= 200000
