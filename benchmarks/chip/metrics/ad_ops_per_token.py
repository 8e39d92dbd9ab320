"""PIM backend: A/D operations (SAR comparator cycles, the Runtime's
``AdOpsReport``, Eq. 6) of every Runtime call in the window, per token
those calls served (real prompt tokens and generated tokens).  Padding
and idle decode rows count in the numerator only."""


def read(ctx):
    return ctx["ad_ops_per_token"]
