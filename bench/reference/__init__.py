"""Plain reference of the replication semantics, independent of the
program: the access walk under each routing policy (paper Eqn 1-2), the
sequential UPDATE of Alg 2, the same-policy prune, and the controller's
window verdicts.  Numpy on the host; it imports nothing of ``repro``."""
