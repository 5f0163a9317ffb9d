"""mem_restore_s (s): the RAM tier's own timing of the restore that serves
the rollback of the injected fault (flight recorder, mem.restore)."""


def read(run):
    secs = [ev["seconds"] for ev in run.flight if ev["kind"] == "mem.restore"]
    return secs[-1] if secs else None
