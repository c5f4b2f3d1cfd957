"""A per-layer metric that is one of the clients' own numbers: the driver's
evidence under ``client`` (``clients/closed_loop.py`` ``summarize``).

args: ``what`` "stat", ``name`` one of the summary's keys.
"""


def read(args, evidence):
    c = evidence.get("client")
    if args["what"] != "stat":
        raise ValueError(f"client reader: what={args['what']!r}")
    return None if not c else c.get(args["name"])
