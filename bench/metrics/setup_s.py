"""Set-up seconds: process start to the window's opening (imports, weights,
the program's deploy pipeline, fleet build, attach, roll-in, warm-up and
every compile).  Host clock."""


def read(ctx):
    return ctx["setup_s"]
