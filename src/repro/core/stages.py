"""Names of the train step's stages.

``make_train_step`` and the sparsify round (``_spa_leaf``,
``compact_select``) open a ``jax.named_scope`` under each of these names.
A scope is metadata only: it lands in the compiled HLO's ``op_name`` (for
example ``jit(step)/train.round/shard_map/spa.select/top_k``), so a device
trace can be split by stage, and the compiled program is otherwise the
same. Under ``DistConfig.overlap="buckets:B"`` the round's stages sit
inside their bucket's ``spa_bucketNNN`` scope; the stage is always the
innermost of these names.

- ``train.grads``: the batch's reshape over the workers, the per-worker
  forward and backward passes, and the cast to the state dtype;
- ``train.round``: the sparsify-and-aggregate round (the ``spa.*`` stages
  and the round's own bookkeeping);
- ``train.optimizer``: the optimizer's update;
- ``spa.score``: the error-fed gradient ``a = eps + g`` and RegTop-k's
  score (``|a|^y``, the posterior at last round's sent coordinates);
- ``spa.select``: top-k (or the threshold selector) and the payload's
  gather; the fused select→encode kernel scores and selects in one pass,
  so all of it is ``spa.select``;
- ``spa.encode``: the codec's encode;
- ``spa.exchange``: the collective between workers (the payload
  strategy's ``shard``, ``dense_allreduce``'s scatter and psum, the
  pmean of kind ``none``);
- ``spa.feedback``: the codec's decode, the sent vector and the error
  state's update for the next round.
"""
GRADS = "train.grads"
ROUND = "train.round"
OPTIMIZER = "train.optimizer"
SCORE = "spa.score"
SELECT = "spa.select"
ENCODE = "spa.encode"
EXCHANGE = "spa.exchange"
FEEDBACK = "spa.feedback"

ALL = (GRADS, ROUND, OPTIMIZER, SCORE, SELECT, ENCODE, EXCHANGE, FEEDBACK)
