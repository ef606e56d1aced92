"""Model families built on the device plane.

The reference is a communication library; its "models" are the
applications above it. A TPU-native framework carries the model layer
in-tree because the parallelism strategies (SURVEY.md §2.10) only
mean something when compute hangs off them: the flagship transformer
(:mod:`ompi_tpu.models.transformer`) exercises dp (gradient psum),
tp (Megatron column/row sharding + psum), sp (ring attention over the
ICI ring) and ep (MoE all_to_all) in one training step. Import the
module you use: ``remat`` <- ``vision`` <- ``params`` <-
``transformer`` <- ``pipeline``; nothing is loaded for you (the tower
and the rule load without the decoder).
"""
