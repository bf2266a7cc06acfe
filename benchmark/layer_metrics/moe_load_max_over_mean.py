"""Rows of the fullest held expert over the mean of the held experts: the
program's own counter (``models/moe.py`` ``RoutedExperts``, averaged over the
layers by the model and carried into ``step_metrics`` by
``losses.sparse_moe_lm``), mean over the window's laps. A lap's value is that
of the lap's last step. 1 is perfectly even routing; a program that lacks the
counter gives nothing."""

NAME = "moe_load_max_over_mean"


def read(ctx):
    values = [e["metrics"][NAME] for e in ctx["laps"]
              if NAME in (e.get("metrics") or {})]
    if not values:
        return None
    ctx["facts"][NAME] = {"laps": len(values), "min": min(values),
                          "max": max(values)}
    return sum(values) / len(values)
