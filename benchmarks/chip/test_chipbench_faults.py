"""``correct`` separates a sound run from a broken one.

The harness runs end to end on the CPU at a tiny size (it skips its look
for a chip): a sound run is correct; the same run with the fp8 control in
the program's place is not; and a run whose served tokens are altered
where the engine produces them is not correct.  The limit here is the
tiny size's own; the cells' limits are in ``cells/`` (``PERF.md``).
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "src"))

import chipbench_tiny as tiny                              # noqa: E402
from chipbench import harness                              # noqa: E402

LIMIT = 0.1     # tiny sizes: sound runs read about 0.01-0.03, fp8 0.3


def _cell(kind, traffic_kind):
    return tiny.cell(kind, traffic_kind, LIMIT)


@pytest.mark.parametrize("kind,traffic_kind", [("mla", "sessions"),
                                               ("gqa_moe", "chat")])
def test_sound_run_is_correct_and_the_control_is_not(kind, traffic_kind):
    cell = _cell(kind, traffic_kind)
    out = harness.run(cell, 2**31 + 11, 3.0, False, allow_cpu=True)
    assert out["correct"], (out["check"], out["_info"])
    assert out["check"]["max_logit_gap"]["value"] <= LIMIT
    assert set(out["metrics"]) >= {"setup_s", "tpot_p50_ms",
                                   "output_tokens_per_s"}
    # every live session is compared, each at several positions
    if traffic_kind == "sessions":
        assert len(out["_info"]["program_gap_per_request"]) == \
            out["attempted"] > 1
    ctrl = harness.run(cell, 2**31 + 11, 3.0, False, control=True,
                       allow_cpu=True)
    assert not ctrl["correct"], (ctrl["check"], ctrl["_info"])
    assert ctrl["check"]["max_logit_gap"]["value"] > LIMIT
    assert ctrl["_info"]["program_max_logit_gap"] <= LIMIT


def test_altered_token_is_not_correct(monkeypatch):
    from repro.serving import engine as engine_mod
    orig = engine_mod.NanoCPEngine._harvest

    def altered(self, now):
        infl = self._inflight
        done = orig(self, now)
        for rid, *_ in (infl.slots if infl is not None else ()):
            toks = self.results[rid].tokens
            toks[-1] = (toks[-1] + 1) % self.cfg.vocab_size
            self.next_tok[rid] = toks[-1]
        return done

    monkeypatch.setattr(engine_mod.NanoCPEngine, "_harvest", altered)
    out = harness.run(_cell("mla", "sessions"), 2**31 + 12, 3.0, False,
                      allow_cpu=True)
    assert not out["correct"]
    assert out["check"]["max_logit_gap"]["value"] > LIMIT


def test_no_chip_no_result():
    with pytest.raises(harness.NoChip):
        harness.require_chips(1)
