"""The run's diagnostics: the program's private seams fail by name, the
host watch accounts for a pause, and the serving tails' make-up groups
gaps by the admissions inside them."""
from __future__ import annotations

import time

import numpy as np
import pytest

from chipbench import common as cm
from chipbench import serve


def test_a_missing_seam_fails_by_name():
    class Engine:
        _step = 1

    assert cm.seam(Engine(), "_step") == 1
    with pytest.raises(RuntimeError, match=r"Engine\._admit_one"):
        cm.seam(Engine(), "_admit_one")


def test_host_watch_accounts_for_a_busy_pause():
    watch = cm.HostWatch(every=0.02)
    t0, c0 = cm.now(), time.process_time()
    while time.process_time() - c0 < 0.2:   # the process busy on the host
        pass
    t1 = cm.now()
    time.sleep(0.1)
    watch.close()
    text = watch.across(t0, t1)
    cpu = float(text.split("process CPU ")[1].split(" s")[0])
    assert cpu >= 0.2
    assert "longest sampler tick" in text
    line = cm.longest_pause([t0 - 0.1, t0, t1], t0 - 0.1, watch)
    assert line.startswith(f"longest pause between completions "
                           f"{t1 - t0:.3f} s")
    assert "in it:" in line and "over the window:" in line


def test_tails_group_gaps_by_admissions():
    stats = {"ttft_s": [0.1, 0.2, 0.3], "queue_wait_s": [0.05, 0.06, 0.07],
             "itl_s": [0.1, 0.1, 0.12, 0.15, 0.2],
             "itl_admits": [0, 0, 1, 3, 6]}
    text = serve.tails(stats)
    assert "0: 40.0% median 100.0" in text
    assert "1: 20.0% median 120.0" in text
    assert "3: 20.0% median 150.0" in text
    assert "4+: 20.0% median 200.0" in text
    assert "2:" not in text
    assert f"{1e3 * np.percentile(stats['itl_s'], 95):.1f}" in text


def test_longest_pause_names_how_its_interval_was_spent():
    line = cm.longest_pause([0.0, 0.2, 1.5, 1.7], 0.0,
                            detail=["", "input", "device wait 1.3 s", "x"])
    assert line.startswith("longest pause between completions 1.300 s at "
                           "0.200 s into the window")
    assert "(device wait 1.3 s)" in line
