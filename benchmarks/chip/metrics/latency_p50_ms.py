"""Median due-to-answer time over every request due in the window; an
unanswered request counts as missing (host clock)."""
import drive
import stats


def read(run):
    if run.mode != "open":
        return None
    lat = drive.latencies_ms(run.log, run.seconds)
    return stats.percentile(lat, 50) if len(lat) else None
