"""Zero-copy intra-node RMA (shared-segment Win.Allocate path)."""

import os
import re

from tests.test_process_mode import run_mpi


def test_osc_shm_procmode_4ranks():
    r = run_mpi(4, "tests/procmode/check_osc_shm.py", timeout=160)
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("OSCSHM-OK") == 4, r.stdout
    m = re.search(r"ratio=([0-9.]+)", r.stdout)
    assert m, r.stdout
    # performance-ratio floor only under the soak/bench gate: on the
    # loaded shared CI host scheduler noise can flake it (ADVICE r4);
    # the correctness assertions above are unconditional
    if os.environ.get("OMPI_TPU_TEST_SOAK"):
        # one mapped memcpy vs frame copy + round trip: decisive even
        # on a loaded single-core host (measured ~69x)
        assert float(m.group(1)) >= 3.0, r.stdout
