"""One module per kind of client, found by the ``clients`` key of a mix.

A module defines ``Client(server, table, build, words, mix, wait_s)`` with:

* ``warm_up(seed)`` — send the shapes the window will send, once;
* ``start(seed)`` — begin the window's traffic, drawn from ``seed``;
* ``step(keep) -> (records, kept)`` — serve the next unit of traffic and
  wait for it: the :class:`bench.harness.ReadRecord` of every read it sent,
  and the answers of those reads whose template ``keep(name)`` accepts, for
  the correctness check;
* ``stop()`` — end the traffic and wait for everything it started;
* ``report() -> list[str]`` — lines for standard error (how late the client
  ran, how long its steps took).

The harness calls ``step`` until the window closes, so a step that returns
blocks until its reads are answered (or have failed).
"""
