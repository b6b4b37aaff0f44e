"""`q18.check` against the reference's own answer at a lowered QUANTITY
(SF 0.05: the spec's 312..315 leave no row at that size): it accepts the
answer and refuses each way an answer can be wrong; the control's float32
reading is refused there too.  `faulty_run.py` is fixed to SF 0.01 and the
workload's own QUANTITY, where Q18's answer is empty, so its planted faults
cannot show on the cell `tpch_sf1_q18.q18`: these cases stand in for them."""
import numpy as np
import pytest

import control
import datagen
import run

SF = 0.05
PARAMS = {"quantity": 200}


@pytest.fixture(scope="module")
def q18():
    return run.load_module("queries", "q18")


@pytest.fixture(scope="module")
def ref(q18):
    (answer,), rows = q18.reference(datagen, SF, [PARAMS])
    assert len(answer) > q18.LIMIT, "the cut to LIMIT has to cut something"
    assert rows == {"customer": 7500, "orders": 75000,
                    "lineitem": sum(n for n in datagen.map_lineitem(
                        SF, ["l_orderkey"], lambda v: len(v["l_orderkey"])))}
    return answer


def test_the_references_own_answer_is_accepted(q18, ref):
    assert q18.check(ref[:q18.LIMIT], ref)
    assert [(-r[4], r[3]) for r in ref] == sorted((-r[4], r[3]) for r in ref)


def engine_rows(rows):
    """The reference's encoding as the engine returns it: o_totalprice a
    python float, the sum a Decimal, the date ISO text."""
    import decimal

    from _rows import iso

    return [(r[0], r[1], r[2], iso(r[3]), r[4] / 100,
             decimal.Decimal(r[5]).scaleb(-2)) for r in rows]


def test_the_engines_encoding_is_accepted(q18, ref):
    assert q18.check(engine_rows(ref[:q18.LIMIT]), ref)


def dropped(rows, ref):
    return rows[:40] + rows[41:]


def price_altered(rows, ref):
    r = rows[7]
    return rows[:7] + [r[:4] + (r[4] + 1,) + r[5:]] + rows[8:]


def sum_altered(rows, ref):
    r = rows[99]
    return rows[:99] + [r[:5] + (r[5] + 100,)]


def name_altered(rows, ref):
    r = rows[0]
    return [("Customer#%09d" % (r[1] + 1),) + r[1:]] + rows[1:]


def swapped(rows, ref):
    assert (rows[3][4], rows[3][3]) != (rows[4][4], rows[4][3])
    return rows[:3] + [rows[4], rows[3]] + rows[5:]


def one_more(rows, ref):
    return rows + [ref[len(rows)]]


def repeated(rows, ref):
    return rows[:50] + [rows[49]] + rows[51:]


def a_fraction_of_a_cent(rows, ref):
    rows = engine_rows(rows)
    r = rows[0]
    return [r[:4] + (r[4] + 0.001,) + r[5:]] + rows[1:]


@pytest.mark.parametrize("fault", [
    dropped, price_altered, sum_altered, name_altered, swapped, one_more,
    repeated, a_fraction_of_a_cent])
def test_a_wrong_answer_is_refused(q18, ref, fault):
    assert not q18.check(fault(list(ref[:q18.LIMIT]), ref), ref)


def test_a_tie_may_come_in_either_order(q18):
    a = ("Customer#000000001", 1, 10, 9000, 500, 20100)
    b = ("Customer#000000002", 2, 11, 9000, 500, 20200)
    c = ("Customer#000000004", 4, 12, 9001, 400, 20300)
    assert q18.check([b, a, c], [a, b, c])
    assert not q18.check([a, c, b], [a, b, c])


def test_the_float32_control_is_refused(q18):
    assert control.control_passes(q18, SF, [PARAMS, {"quantity": 250}]) == [
        False, False]
    # the sums are exact in float32 (at most 35,000 hundredths): it is
    # o_totalprice, cents above 2^24, that the control loses
    (low,), _ = q18.reference(datagen, SF, [PARAMS], acc=np.float32)
    (exact,), _ = q18.reference(datagen, SF, [PARAMS])
    assert sorted(r[5] for r in low) == sorted(r[5] for r in exact)
    assert {r[4] for r in low} != {r[4] for r in exact}
