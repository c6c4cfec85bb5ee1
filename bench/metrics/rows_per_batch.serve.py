"""Mean query points per coalesced device batch in the window, from the
front door's own report (``FrontDoor.report()["batches"]``)."""


def read(run):
    batches = run.counters.get("frontdoor", {}).get("batches", {})
    return batches.get("rows_per_batch_mean") if batches.get("count") else None
