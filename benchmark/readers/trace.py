"""A per-layer metric from the reduced device trace (``trace_reduce``).

args: ``what`` "idle_share" (1 - busy / traced window, %) | "busy_mfu" (the
driver's FLOPs per unit of work over the device-busy seconds that unit
took, as a share of ``peak`` of ``peaks.json``, %).  Busy seconds per unit
are the trace's busy seconds scaled by units per second of the driver's
window, so a profile that starts or stops inside a step does no harm.  No
trace in this run: nothing to read.
"""


def read(args, evidence):
    t = evidence.get("trace")
    if not t or not t["window_s"]:
        return None
    if args["what"] == "idle_share":
        return (1.0 - t["busy_s"] / t["window_s"]) * 100.0
    if args["what"] == "busy_mfu":
        w = evidence.get("window")
        if not w or not w["units"] or not t["busy_s"] \
                or not evidence.get("peaks"):
            return None
        units_traced = w["units"] / w["seconds"] * t["window_s"]
        busy_per_unit = t["busy_s"] / units_traced
        # flops_per_unit is the whole step over all chips; busy_s is per chip
        return (w["flops_per_unit"] / w["chips"] / busy_per_unit
                / evidence["peaks"][args["peak"]] * 100.0)
    raise ValueError(f"trace reader: what={args['what']!r}")
