"""Distributed policy package: sharding rules for the launch-time steps.

`repro.dist.sharding` maps param/cache pytrees to PartitionSpecs under a
small rule object (`ShardRules`).  The training loop's straggler watchdog
lives with the loop (`repro.launch.train.StepWatchdog`).
"""
