"""Video-seconds whose golden and derived formats were durably written,
over the seconds from the window's start until the last of them was."""


def read(run):
    if not run.records or run.window_s <= 0:
        return None
    done = sum(r["video_s"] for r in run.records if r.get("ok"))
    return done / run.window_s
