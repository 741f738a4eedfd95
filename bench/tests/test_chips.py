"""Shares of a peak over a cell's chips: a one-chip ``Run`` reads
``decode_roofline`` and ``mfu_pct`` as the one chip's peaks give them,
and a four-chip one holds the same work against four chips' peaks."""
import dataclasses
from types import SimpleNamespace as NS

import pytest

import flops
import spec
from devtrace import Trace
from driver import Step
from record import Run
from test_trace import DATA


def run_on(chips):
    cell = spec.load_cell("granite-8b-pp2.batch")
    cell = dataclasses.replace(cell, chips=chips)
    steps = [Step(0.1 * i, 0.1 * i + 0.07, 32, 32 * 900,
                  chunks=[(0, 512)] if i == 1 else []) for i in range(3)]
    win = NS(steps=steps, served=[])
    return Run(cell=cell, seconds=1.0, window=win, setup_s=1.0,
               compile_s=0.5, peaks=flops.peaks("TPU v5 lite"),
               trace=Trace.load(DATA / "trace_batch.json.gz"),
               traced=(0, 3))


def read(name, run):
    return spec.reader("metrics", name)(run)


def test_one_chip_reads_as_the_chip_peaks_give():
    run = run_on(1)
    conf, p = run.config, flops.peaks("TPU v5 lite")
    assert run.peaks == p
    f, b = flops.decode_step(conf, 32, 32 * 900)
    need = 3 * max(f / p["bf16_flops_per_s"], b / p["hbm_bytes_per_s"])
    dur = sum(run.trace.modules("fused")) * 1e-9
    assert read("decode_roofline", run) == pytest.approx(100 * need / dur)
    work = 3 * f + flops.chunk(conf, 0, 512)[0]
    assert read("mfu_pct", run) == pytest.approx(
        100 * work / (3 * 0.07 * p["bf16_flops_per_s"]))


def test_four_chips_divide_by_four_chips_peaks():
    one, four = run_on(1), run_on(4)
    assert four.peaks["hbm_bytes_per_s"] == 4 * one.peaks["hbm_bytes_per_s"]
    assert four.peaks["hbm_bytes"] == one.peaks["hbm_bytes"]
    for name in ("decode_roofline", "mfu_pct"):
        assert read(name, four) == pytest.approx(read(name, one) / 4)
