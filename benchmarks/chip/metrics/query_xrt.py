"""Video-seconds of the requests answered, over the seconds from the
window's first request to its last answer."""


def read(run):
    if not run.records or run.window_s <= 0:
        return None
    done = sum(r["result"].video_seconds for r in run.records if r.get("ok"))
    return done / run.window_s
