"""Bytes stored, all formats, per video-second ingested in the window
(the store's own ingest accounting)."""


def read(run):
    if run.video_s <= 0:
        return None
    return run.stored_bytes / run.video_s
