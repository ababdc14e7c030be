"""Rows routed to held experts over the rows the expert products are issued
over, mean over the measured window's steps and the layers: the program's
``model.moe.rows_held`` / ``model.moe.rows_buffer`` counters, which the job
publishes from the step statistics once the window has closed."""


def read(run):
    publish = getattr(run["job"], "publish_moe_stats", None)
    if publish is None:
        return None
    counters = publish(run["log"].steps)
    if not counters.get("rows_buffer"):
        return None
    return 100.0 * counters["rows_held"] / counters["rows_buffer"]
