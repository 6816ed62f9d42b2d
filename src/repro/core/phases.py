"""Names of the lazy step's phases, each a ``jax.named_scope`` around the
code that does it.  A scope changes only the compiled program's metadata
(every op's ``op_name`` carries it, e.g. ``.../while/body/lazy.scatter/
scatter``), never its ops, so a profile of the round program can be split
by phase without changing what runs.

* ``GATHER``: everything read before the update — the touched rows'
  gather, and for the cache-based solvers the DP-cache extend and the
  catch-up factors;
* ``KERNEL``: the update itself — the backend's fused whole-step op, and
  on a feature mesh the shard-local margin op, the margin psum, the
  gradient and (for FTRL) the AdaGrad deltas;
* ``SCATTER``: the write-back — the scatter-SET/ADD into the state and the
  bias step;
* ``FLUSH``: the round flush (``core.linear_trainer.flush``,
  ``dist.linear.local_flush``).

``MARGIN`` is no fifth phase but a scope inside ``KERNEL``: on a feature
mesh, the per-example margin's cross-shard reduction
(``dist.linear.margin_psum``: the psum and the ops around it), so that a
profile can read the collective's own device time.  Single-device
programs hold no psum and never name it.
"""

GATHER = "lazy.gather"
KERNEL = "lazy.kernel"
SCATTER = "lazy.scatter"
FLUSH = "lazy.flush"
PHASES = (GATHER, KERNEL, SCATTER, FLUSH)
MARGIN = "lazy.margin"
