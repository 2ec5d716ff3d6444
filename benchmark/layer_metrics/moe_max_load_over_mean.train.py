"""The fullest held expert's pairs over the mean held expert's, worst
layer, of the window's last step: the program's own routing counter. A
dropless layer's tiles follow its fullest expert's padding and its time
the pairs, so this is what an uneven routing costs."""


def read(ctx):
    routing = ctx["counters"].get("routing")
    return routing["max_load_over_mean"] if routing else None
