"""Mean gigabytes (1e9 bytes) a migration of the window sends: the first
full copy, every round's dirty blocks and the stop-and-copy
(``PrecopyReport.outcome.bytes_sent``, a counter of the hybrid cell's
driver)."""


def read(run):
    return run.counters.get("sent_gb_per_migration")
