"""Plain references that a configuration names with its ``reference`` key.

Each module here exposes ``simulate(lanes, cfg, ftype=..., block=...)``
with the contract of :func:`perfbench.lib.reference.simulate`, which a
configuration without the key is compared with.  A reference of another
placement or gating policy imports that base and supplies only its rule,
for example::

    import functools

    from perfbench.lib import reference

    def rack_rank(free, m):
        ...  # ascending key: the servers an admission fills first

    simulate = functools.partial(
        reference.simulate, ranks={**reference.RANKS, "lwf_rack": rack_rank})

Like the base, a module is written from the policy's description and
imports nothing of the program.
"""
