"""Mean time a request waits between admission and the start of its
batch's dispatch (the queue plus the batching window), from the front
door's own counters (``FrontDoor.report()["stages"]["queue_wait"]``), in
ms."""


def read(run):
    stage = run.counters.get("frontdoor", {}).get("stages", {}).get("queue_wait", {})
    return stage.get("mean_ms") if stage.get("count") else None
