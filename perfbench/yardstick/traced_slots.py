"""Mean slots in decode over a run's traced span, from the job's own 50 ms
samples of ``ServingEngine.stats()``: the requests' records know only the
answers that arrived, and in a cell that cuts its streams at the shutdown
most of those that decode through the traced tail never answer."""


def decoding_slots(run):
    span, job = run.get("traced_span_client"), run["job"]
    if not span or not job.get("occupancy"):
        return None
    zero = run["stages"].at("schedule_start")
    rows = [r[1] for r in job["occupancy"]
            if zero + span[0] <= r[0] < zero + span[1]]
    return sum(rows) / len(rows) if rows else None
