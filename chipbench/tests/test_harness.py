"""The harness end to end on the CPU, at smoke sizes with interpret-mode
kernels: a closed and an open loop, cells added from files alone, the
censored TTFT tail, and the command's refusal of anything but a TPU."""
import json
import os
import subprocess
import sys
import time

import pytest

from cbench import derive, spec
from cbench.harness import run_cell
from cbench.loop import Req, Step
from cbench.traffic import Draw, Stream

from conftest import BENCH, ROOT

SEED = 2**31 + 4242
E2E = {"itl_p95_ms", "tokens_per_s", "setup_s"}


def _run(root, cell, seconds=2.0, trace=False, **kw):
    return run_cell(spec.load(root, cell), SEED, seconds, trace,
                    time.perf_counter(), **kw)


def test_closed_loop_cell_from_files(smoke_root):
    out = _run(smoke_root(loop="closed"), "smoke.closed")
    assert out["correct"] is True
    assert set(out["metrics"]) == E2E
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "check"
    assert out["check"]["tokens_checked"]["value"] >= 10
    json.dumps(out)


def test_open_loop_cell(smoke_root):
    root = smoke_root(kind="mla", loop="open")
    out = _run(root, "smoke.open", seconds=3.0)
    assert out["correct"] is True
    # Poisson at 4 requests/s: every request the seed makes due within the
    # 3 s counts (and those due while the last step ran past the close)
    cell = spec.load(root, "smoke.open")
    draws = Stream(cell.mix, SEED, 256)
    due = next(i for i in range(1000) if draws[i].offset_s > 3.0)
    assert 0 < due <= out["attempted"] <= due + 4
    assert out["metrics"]["itl_p95_ms"]["value"] > 0


def test_censored_ttft_and_window_gaps():
    """A request still without its first token enters with its wait so
    far; requests due before the window are not counted."""
    d = Draw(prompt=[1, 2, 3], max_new=4, offset_s=0.0)
    reqs = [Req(d, None, t_due=0.5, t_sent=0.5),                        # stalled
            Req(d, None, t_due=1.0, t_sent=1.0, t_first=1.2, times=[1.2, 1.2, 1.5]),
            Req(d, None, t_due=-1.0, t_sent=-1.0, t_first=0.1, times=[0.1, 0.4])]
    ctx = derive.Context(model={}, mix={}, reqs=reqs, steps=[], window=(0.0, 2.0),
                         setup_s=1.0, compiles_in_window=0, peaks=None)
    assert sorted(derive.ttfts_s(ctx)) == pytest.approx([0.2, 1.5])
    assert sorted(derive.token_gaps_s(ctx)) == pytest.approx([0.0, 0.3, 0.3])
    assert derive.tokens_in_window(ctx) == 5


def test_per_layer_metric_from_new_file(smoke_root):
    """A later cell brings a per-layer metric as a file of its own."""
    root = smoke_root(per_layer=[
        {"name": "serve.batch_occupancy", "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "Serving", "moves": "tokens_per_s"},
        {"name": "smoke.steps", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "Serving", "moves": "tokens_per_s"}])
    (root / "chipbench" / "metrics" / "smoke.steps.py").write_text(
        "from cbench import derive\n\n\ndef read(ctx):\n"
        "    return len(derive.window_steps(ctx))\n")
    cell = spec.load(root, "smoke.closed")
    assert [m["name"] for m in cell.per_layer] == ["serve.batch_occupancy", "smoke.steps"]
    out = run_cell(cell, SEED, 2.0, False, time.perf_counter())
    assert set(out["metrics"]) == E2E     # trace 0 reports end-to-end only
    steps = [Step(1.0, 1.1, 3, [], [5, 6, 7]), Step(1.1, 1.2, 3, [], [6, 7, 8]),
             Step(5.0, 5.1, 3, [], [7, 8, 9])]
    ctx = derive.Context(model={}, mix={"max_slots": 3}, reqs=[], steps=steps,
                         window=(1.0, 2.0), setup_s=0.0, compiles_in_window=0,
                         peaks=None)
    assert spec.reader(root, "smoke.steps")(ctx) == 2


def test_command_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        "stablelm-3b.decode-backlog", "--seed", str(SEED),
                        "--seconds", "10", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_command_refuses_a_tree_without_the_program(tmp_path):
    (tmp_path / "chipbench").symlink_to(BENCH)
    (tmp_path / "BENCHMARK.json").write_text((ROOT / "BENCHMARK.json").read_text())
    p = subprocess.run([sys.executable, str(tmp_path / "chipbench" / "run.py"),
                        "--workload", "stablelm-3b.decode-backlog", "--seed", "1",
                        "--seconds", "10", "--trace", "0"],
                       cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class FakeSession:
    """One slot; a step takes 1 s, admits one request and emits one token
    per live request (the admitted one gets its prefill token too)."""

    def __init__(self, clock):
        self.clock, self.slots, self.pending, self.uid = clock, [None], [], 0

    def submit(self, prompt, max_new_tokens):
        class R:
            pass
        r = R()
        r.uid, r.out_tokens, r.done, r.max_new = self.uid, [], False, max_new_tokens
        self.uid += 1
        self.pending.append(r)
        return r

    def step(self):
        self.clock.t += 1.0
        if self.slots[0] is None and self.pending:
            self.slots[0] = self.pending.pop(0)
            self.slots[0].out_tokens.append(0)
        r = self.slots[0]
        if r is None:
            return 0
        r.out_tokens.append(0)
        if len(r.out_tokens) >= r.max_new:
            r.done, self.slots[0] = True, None
        return 1


def test_open_loop_times_from_due_and_waits_when_idle():
    from cbench.loop import Loop
    clock = FakeClock()
    mix = {"loop": "open", "max_slots": 1}
    draws = [Draw([1, 2], 3, 0.5), Draw([1, 2, 3], 3, 0.6), Draw([1], 2, 9.0)]
    lp = Loop(FakeSession(clock), mix, draws, clock=clock, sleep=clock.sleep)
    t0, t1 = lp.run(5.0)
    a, b = lp.reqs
    # idle until 0.5, then a: admitted at once, done after 2 steps (3 tokens)
    assert a.t_due == t0 + 0.5 and a.t_first == t0 + 1.5
    # b was due at 0.6 but waits behind a: its TTFT counts from 0.6
    assert b.t_due == t0 + 0.6 and b.t_first == t0 + 3.5
    assert [len(r.times) for r in (a, b)] == [3, 3]
    assert lp.steps[0].prefill_lens == [2] and lp.steps[0].decode_lens == [3]
    ctx = derive.Context(model={}, mix=mix, reqs=lp.reqs, steps=lp.steps,
                         window=(t0, t1), setup_s=0.0, compiles_in_window=0,
                         peaks=None)
    assert sorted(derive.ttfts_s(ctx)) == pytest.approx([1.0, 2.9])
