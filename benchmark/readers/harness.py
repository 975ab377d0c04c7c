"""Reader ``harness``: a number the harness itself took in the window
(``ctx.window``), such as how late the generator ran."""


def read(args, ctx):
    return ctx.window.get(args["key"])
