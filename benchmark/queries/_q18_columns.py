"""The two columns TPC-H Q18 reads that `datagen.py` does not copy, made the
way the program's host generator makes them (`trino_tpu/connectors/tpch.py`:
`_Gen.orders`, `_Gen.customer`): a pure function of the row counter, through
the `data` module the reference is handed.  It imports nothing of the
program; `tests/test_benchmark_q18.py` holds it to the original."""
import numpy as np


def o_totalprice(data, sf, lo=0, hi=None):
    """Cents, uniform in [1,000.00, 500,000.00] by the order's counter: the
    generator's draw, not the sum over the order's lines (cl.4.2.3)."""
    n = data.counts(sf)["orders"]
    j = np.arange(lo, n if hi is None else hi, dtype=np.int64)
    return data.uint_in("o_totalprice", j, 100000, 50000000)


def c_name(custkeys):
    """`Customer#` and the key in nine digits (cl.4.2.3)."""
    return ["Customer#%09d" % k for k in np.asarray(custkeys).tolist()]
