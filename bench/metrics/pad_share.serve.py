"""Server (serving/elm_server.py): padded rows over all rows launched in
the window, from the server's own counters."""


def read(ctx):
    c = ctx.counters
    launched = c["rows"] + c["padded_rows"]
    return 100.0 * c["padded_rows"] / launched if launched else None
