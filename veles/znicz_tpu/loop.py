"""Units that run several times in one compiled step over ONE set of
parameters (``root.lm.model.ut_steps``): :class:`Loop`.

``veles/accelerated_units.py`` knows only that a workflow may hand the
step's units to such a loop (``StepCompiler.loop``) and what a VISIT
is (``FlowContext.visit`` / ``defer``: a child context per run of a
unit, gradients summed instead of applied). Everything that knows this
package's conventions lives here, beside the units that define them —
``GradientDescentBase`` (``forward``, ``err_output`` / ``err_input``,
``apply_grads``), ``VjpForward`` (``BUFFERS``, pullbacks a visit may
skip) and the evaluator's ``loop_begin`` / ``loop_end``.
"""


class Loop:
    """Units that run ``steps`` times a step over ONE set of
    parameters: a stack of layers applied again to its own output (the
    passes), with every pass's state read by the same exit units and
    one loss over all the exits.

    ``segments``: the forward units of one pass, in order, as the runs
    that are recomputed together — a segment's input is the only thing
    a pass keeps for the backward, which runs the segment's forward
    again (``veles.recompute``) to have its pullbacks. ``exits``: the
    forward units applied to each pass's state after the passes, up to
    the evaluator. A body unit's ``TAPS`` are values of each pass that
    the evaluator wants beside the exits (a gate); their cotangents
    come back to the unit's gradient half as ``err_<tap>``. A body unit
    that sets ``kept`` in a forward pass's visit (one whose
    ``pullbacks`` is False) declares a value its recomputation may take
    from that pass instead of making it again: the loop stacks it out
    of the passes, sets it in the unit's recompute visit of the same
    pass before the unit runs there, and exports ``kept_<unit>``, the
    recomputed applications a step that took it (a static count).

    The step it traces, every unit under its usual scope
    ``veles.<role>.<Class>.<name>`` in a visit of the step's context
    (:meth:`FlowContext.visit`):

    1. the passes, a ``lax.scan``: forward units alone, each
       segment's input, the pass's state, taps and kept values stacked
       out;
    2. the exits, a ``lax.scan`` over the passes' states: exit units,
       the evaluator's visit (``xla_run``, fed what its ``loop_begin``
       made of the taps) and the exits' gradient units — one exit's
       logits live at a time; then the evaluator's ``loop_end`` closes
       the loss over all exits;
    3. the passes backward, a ``lax.scan`` in reverse: per segment the
       recomputed forward (handed the pass's kept values), then its
       gradient units in reverse; the state's cotangent is the carry,
       the exit's is added on entry;
    4. ONE update: every looped gradient unit's solver on the float32
       sum of its visits' gradients (``veles.update``).

    The passes are iterations of one compiled loop body, so a device
    trace has no pass number in a path: both pass loops run under
    ``veles.pass``.
    """

    def __init__(self, steps, segments, exits):
        self.steps = int(steps)
        self.segments = [list(units) for units in segments]
        self.body = [u for units in self.segments for u in units]
        self.exits = list(exits)

    @staticmethod
    def taps(units):
        return [(u, tap, "%s.%s" % (u.name, tap))
                for u in units for tap in getattr(u, "TAPS", ())]

    @staticmethod
    def zeros(ctx, gds):
        """A float32 accumulator for every trainable array of the
        gradient units' forwards."""
        import jax.numpy as jnp
        return {gd.name: {
            name: jnp.zeros(value.shape, jnp.float32)
            for name, value in ctx.unit_params(gd.forward).items()
            if name not in getattr(gd.forward, "BUFFERS", ())}
            for gd in gds}

    @staticmethod
    def pinned(v, units, h):
        """Tie the parameters the visit ``v`` reads to the loop's
        carry ``h`` (an ``optimization_barrier``, which costs nothing):
        a parameter is the same in every iteration, so XLA makes its
        compute-type and transposed copies ONCE, before the loop, and
        keeps them all through it. Tied, the transposed ones are made
        where they are used (``benchmark/rehearse.py``, 8 layers of
        51M: 6.19 against 6.97 GB of temporaries; the plain casts still
        move across the barrier and out of the loop). -> h."""
        import jax
        mine = {u.name: v.params[u.name] for u in units
                if u.name in v.params}
        mine, h = jax.lax.optimization_barrier((mine, h))
        v.params.update(mine)
        return h

    def trace(self, compiler, ctx, units):
        import jax
        import jax.numpy as jnp
        run = compiler.run_unit
        looped = set(self.body + self.exits)
        evaluator = next(u for u in units if u.scope_role == "loss")
        gd_of = {u.forward: u for u in units
                 if getattr(u, "forward", None) in looped}
        train = bool(gd_of)
        mine = looped | {evaluator} | set(gd_of.values())
        first = units.index(self.body[0])
        for unit in units[:first]:
            run(ctx, unit)
        taps = self.taps(self.body)

        def forward_pass(h, _):
            v = ctx.visit(pullbacks=False)
            h = self.pinned(v, self.body, h)
            saved = []
            with jax.named_scope("veles.pass"):
                for segment in self.segments:
                    saved.append(h)
                    v.set(segment[0], "input", h)
                    for unit in segment:
                        run(v, unit)
                    h = v.get(segment[-1], "output")
            tapped = {key: v.get(u, tap) for u, tap, key in taps}
            kept = {u.name: v.values[(u.name, "kept")] for u in self.body
                    if train and (u.name, "kept") in v.values}
            return h, (tuple(saved) if train else (), h, tapped, kept)

        h0 = ctx.get(self.body[0], "input")
        _, (saved, states, tapped, kept) = jax.lax.scan(
            forward_pass, h0, None, length=self.steps)
        for unit, tap, key in taps:
            ctx.set(unit, tap, tapped[key])
        for name in kept:
            ctx.export("kept_" + name, jnp.int32(self.steps))

        exit_gds = [gd_of[u] for u in reversed(self.exits)] \
            if train else []

        def exit_visit(acc, xs):
            h, feed = xs
            v = ctx.visit(grads=acc)
            h = self.pinned(v, self.exits, h)
            v.set(self.exits[0], "input", h)
            for unit in self.exits:
                run(v, unit)
            for attr, value in feed.items():
                v.set(evaluator, attr, value)
            run(v, evaluator)
            for gd in exit_gds:
                run(v, gd)
            dh = v.get(exit_gds[-1], "err_input") if train else ()
            return v.grads, (dh, v.outputs)

        exit_acc, (dh_exits, exports) = jax.lax.scan(
            exit_visit, self.zeros(ctx, exit_gds),
            (states, evaluator.loop_begin(ctx)))
        with compiler.unit_scope(evaluator):
            dtaps = evaluator.loop_end(ctx, exports)
        if not train:
            return

        def backward_pass(carry, xs):
            dh, acc = carry
            saved, dh_exit, dtapped, kept = xs
            v = ctx.visit(grads=acc)
            dh = (dh.astype(jnp.float32) + dh_exit.astype(jnp.float32)) \
                .astype(dh.dtype)
            dh = self.pinned(v, self.body, dh)
            with jax.named_scope("veles.pass"):
                for segment, x in reversed(list(zip(self.segments,
                                                    saved))):
                    # a segment's repeated forward waits for the
                    # cotangent it will meet: left free, the scheduler
                    # may run every segment's forward first and hold
                    # all their residuals at once
                    handed = {u.name: kept[u.name] for u in segment
                              if u.name in kept}
                    x, dh, handed = jax.lax.optimization_barrier(
                        (x, dh, handed))
                    with jax.named_scope("veles.recompute"):
                        v.set(segment[0], "input", x)
                        for unit in segment:
                            if unit.name in handed:
                                v.set(unit, "kept", handed[unit.name])
                            run(v, unit)
                    v.set(gd_of[segment[-1]], "err_output", dh)
                    for unit in reversed(segment):
                        for _, tap, key in self.taps([unit]):
                            v.set(gd_of[unit], "err_" + tap,
                                  dtapped[key])
                        run(v, gd_of[unit])
                    dh = v.get(gd_of[segment[0]], "err_input")
            return (dh, v.grads), None

        body_gds = [gd_of[u] for u in reversed(self.body)]
        (dh, body_acc), _ = jax.lax.scan(
            backward_pass, (jnp.zeros_like(h0),
                            self.zeros(ctx, body_gds)),
            (saved, dh_exits,
             {key: dtaps[tap] for _, tap, key in taps}, kept),
            reverse=True)
        sums = dict(exit_acc, **body_acc)
        for gd in exit_gds + body_gds:
            if sums[gd.name]:
                with compiler.unit_scope(gd):
                    gd.apply_grads(ctx, sums[gd.name])
        ctx.set(gd_of[self.body[0]], "err_input", dh)
        for unit in units[first:]:
            if unit not in mine:
                run(ctx, unit)
