"""Mean pre-copy rounds of the window's migrations
(``PrecopyReport.outcome.rounds``, a counter of the hybrid cell's driver):
where the stop rules end a migration."""


def read(run):
    return run.counters.get("rounds_per_migration")
