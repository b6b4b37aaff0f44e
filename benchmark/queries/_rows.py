"""Engine rows -> the references' encoding: decimals as exact scaled python
ints, dates as days since 1970-01-01.  Shared by the query files."""
import datetime
import decimal

EPOCH = datetime.date(1970, 1, 1)


def days(iso):
    return (datetime.date.fromisoformat(iso) - EPOCH).days


def iso(day):
    return (EPOCH + datetime.timedelta(days=int(day))).isoformat()


def scaled(x):
    """Decimal / float / int engine value -> exact scaled python int (a
    float is an average the engine rounds to 6 places)."""
    if isinstance(x, decimal.Decimal):
        return int(x.scaleb(-x.as_tuple().exponent))
    if isinstance(x, float):
        return int(decimal.Decimal(repr(x)).scaleb(6).to_integral_value())
    return x


def date(x):
    return days(x) if isinstance(x, str) else x


def total(x, acc):
    """Sum of an integer array: exact (python int) when `acc` is None, else
    accumulated in the float type `acc` as the control does."""
    if acc is None:
        return int(x.sum())
    return acc(x.astype(acc).sum(dtype=acc))


def fold(parts, acc):
    """Sum of per-slice partial sums, in the same arithmetic."""
    out = 0 if acc is None else acc(0)
    for p in parts:
        out = out + p
    return out if acc is None else int(round(float(out)))
