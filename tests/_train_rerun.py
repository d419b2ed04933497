"""Warm-rerun helper for the training loop's compile gates: train once
to build every program, then replay the same config, each run under a
JSONL trace, and read which spans built anything."""
import dataclasses

from repro.obs.trace import read_trace
from repro.train.loop import train_rl_netes


def _spans(path):
    return [r for r in read_trace(path) if r["kind"] == "span"]


def rerun_after_warmup(task, tc, tmp_path):
    """Returns the rerun's history and the span records of both runs."""
    warm, rerun = tmp_path / "warm.jsonl", tmp_path / "rerun.jsonl"
    train_rl_netes(task, dataclasses.replace(tc, trace=str(warm)))
    hist = train_rl_netes(task, dataclasses.replace(tc, trace=str(rerun)))
    return hist, _spans(warm), _spans(rerun)


def _built(r):
    return r["compiles"] + r.get("cache_loads", 0)


def assert_one_scan_per_config(warm_spans, rerun_spans):
    """Only the warm run's first chunk builds a training program: its
    later chunks replay it, and so does every chunk of the rerun. The
    best-agent evaluator is built once per run (``train.loop.
    make_evaluator``), so each run's first ``eval`` span may build one
    program and no other span may build any."""
    for run, spans in (("warm", warm_spans), ("rerun", rerun_spans)):
        chunks = [r for r in spans if r["name"] == "chunk"]
        assert len(chunks) >= 2, run
        assert all(_built(r) == 0 for r in chunks[1:]), (run, chunks)
        evals = [r for r in spans if r["name"] == "eval"]
        assert _built(evals[0]) <= 1, run
        assert all(_built(r) == 0 for r in evals[1:]), run
    assert all(_built(r) == 0 for r in rerun_spans
               if r["name"] != "eval"), rerun_spans
