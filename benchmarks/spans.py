"""The program's telemetry spans (mythril_tpu/support/telemetry), read
back as intervals on the host's monotonic clock."""


def enable(capacity: int) -> None:
    """Record spans from here on, in a ring of `capacity` events."""
    from mythril_tpu.support.telemetry import spans

    spans.configure(capacity=capacity, enable=True)


def recorded() -> list:
    """(start, end, name) in time.monotonic() seconds of every span
    in the ring. Begin/end pairs are matched per thread."""
    from mythril_tpu.support.telemetry import spans

    epoch = spans._EPOCH
    out, open_ = [], {}
    for phase, name, t0, dur, tid, _attrs in spans.snapshot_events():
        t = epoch + t0
        if phase == "X":
            out.append((t, t + dur, name))
        elif phase == "B":
            open_.setdefault((tid, name), []).append(t)
        elif phase == "E" and open_.get((tid, name)):
            out.append((open_[(tid, name)].pop(), t, name))
    return out


def dropped() -> int:
    from mythril_tpu.support.telemetry import spans

    return spans.stats()["dropped"]
