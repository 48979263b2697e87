"""Configuration families: data and weights from the seed, and the program's
problem object built from them.  A configuration file names its family
(`"family": "<name>"` finds `bench/families/<name>.py`).

A family module has `build(config, traffic, seed)`.  For the federated
fine-tuning driver (`bench/drivers/fedlm.py`) the object it returns has:

* `sizes`: the configuration's sizes the family reads;
* `tokens`, `labels`: (clients, sequences, length) int32 on the device;
* `unravel`: the flat iterate -> the parameter tree;
* `problem`: the program's problem object (`FedLMProblem`);
* `x0`: the flat start (the driver drops it after the warm-up call);
* `weights(seed)`: the seeded weights again, as the parameter tree, a dict;
  leaves stacked by layer sit under its `layers` key, which the comparison
  reads layer by layer (`reference.deep_svrp.leaf_norms`);
* `loss(params, tokens, labels, precision)`: the family's plain float32
  reference loss (`bench/reference/<family>.py`, importing nothing of the
  program), its products at `precision` (`bench/reference/precision.py`);
* `forward_flops(sequences, length)`: the FLOPs of one forward pass over
  `sequences` x `length` tokens that the round definition requires
  (`bench/counts.py`), counted from what `build` built, so a family whose
  chip holds a share of the model counts that share.
"""
