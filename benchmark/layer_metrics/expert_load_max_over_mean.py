"""Pairs of the busiest held expert over the mean of the held experts,
last training step, the worst expert layer: the program's gauge
``veles_moe_load_max_over_mean{layer}``. 1 is a perfectly even load;
the grouped products' time follows the sum of the loads, a
deployment's step would follow the busiest chip."""


def read(ctx):
    from veles import telemetry
    values = [child.value
              for family in telemetry.get_registry().families()
              if family.name == "veles_moe_load_max_over_mean"
              for _, child in family.children()]
    return max(values) if values else None
