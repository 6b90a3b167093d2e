"""95th percentile of the time between a request's consecutive tokens,
over the tokens committed inside the window: from the window's delta of
the buckets of ``stats()["loop"]["token_gap"]`` (the upper bound of the
bucket that holds the percentile). Layer: Scheduler."""

from benchmark import loop


def read(run):
    rows = loop.bucket_rows(run, "token_gap")
    return None if rows is None else 1e3 * loop.bucket_percentile(rows, 0.95)
