"""Drive one whole run of the harness on the CPU at SF 0.01 with the timed
path broken underneath, in a process of its own (the faults patch process-
wide state).  `python faulty_run.py <fault> <cell>`; the last stdout line is
the harness's result.  Used by test_faults.py."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run  # noqa: E402


def plant(fault):
    import jax

    jax.config.update("jax_enable_x64", True)
    from trino_tpu import session as S
    from trino_tpu.obs import compile_observatory
    from trino_tpu.utils.metrics import REGISTRY

    real = S.Session.execute
    calls = {"n": 0}

    def execute(self, sql, *a, **kw):
        calls["n"] += 1
        late = calls["n"] > 12       # past set-up: inside the window
        if fault == "half_rows" and late:
            # half of the rows left out of the scan, the rest aggregated
            sql = sql.replace("lineitem\nwhere", "lineitem\nwhere l_orderkey % 2 = 0 and")
        page = real(self, sql, *a, **kw)
        if fault == "compile_in_window" and late:
            compile_observatory.get_observatory().counts["shape_miss"] += 1
        if fault == "cpu_fallback" and late:
            REGISTRY.counter("trino_tpu_device_fallback_total").inc()
        if (fault == "answer_altered" and late) or (
                fault == "unequal_repeats" and late and calls["n"] % 2):
            rows = page.to_pylist()
            rows[0] = rows[0][:-1] + (None,)    # altered where it is produced
            page.to_pylist = lambda: rows
        if fault == "query_raises" and calls["n"] == 20:
            raise RuntimeError("planted")
        return page

    if fault != "none":
        S.Session.execute = execute


if __name__ == "__main__":
    fault, cell = sys.argv[1:3]
    argv = ["--workload", cell, "--seed", "77", "--seconds", "2", "--trace", "0"]
    if fault != "no_chip":
        argv += ["--rehearse-cpu", "--sf", "0.01"]
        plant(fault)
    sys.exit(run.main(argv))
