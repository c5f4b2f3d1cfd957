"""A per-layer metric from the driver's own window (host clock): how long a
unit of work took, or the share of the chip's peak its arithmetic reached.

args: ``what`` "ms_per_unit" | "mfu"; for "mfu", ``peak`` names the entry
of ``peaks.json`` to divide by.  The driver's evidence under ``window``
holds ``seconds``, ``units`` (steps), ``chips`` and ``flops_per_unit``.
"""


def read(args, evidence):
    w = evidence.get("window")
    if not w or not w["units"]:
        return None
    if args["what"] == "ms_per_unit":
        return w["seconds"] / w["units"] * 1e3
    if args["what"] == "mfu":
        if not evidence.get("peaks"):
            return None
        achieved = w["flops_per_unit"] * w["units"] / w["seconds"]
        return achieved / (w["chips"] * evidence["peaks"][args["peak"]]) * 100
    raise ValueError(f"window reader: what={args['what']!r}")
