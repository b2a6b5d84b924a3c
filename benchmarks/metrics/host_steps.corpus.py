"""Instructions the host interpreter executed per analysis: the steps
that the end of each of the program's svm.host_exec spans carries (the
count the program also books into SolverStatistics.host_steps), summed
over the telemetry ring, per analysis completed in the window. The
harness empties the ring as it turns spans on, just before the window,
and runs no analysis after it, so the ring holds the window's stretches
alone. A program whose spans carry no steps reports nothing."""


def read(record):
    from mythril_tpu.support.telemetry import spans

    steps = [attrs["steps"]
             for phase, name, _t, _dur, _tid, attrs
             in spans.snapshot_events()
             if phase == "E" and name == "svm.host_exec"
             and attrs and "steps" in attrs]
    if not steps or not record["completed"]:
        return None
    return sum(steps) / record["completed"]
