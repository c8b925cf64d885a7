"""Operations and bytes that an algorithm requires, worked out from shapes.

Counts are of the model's arithmetic, not of what a program happens to
compute: padding, recomputation and masked-off work are not counted, so
a share of a peak built on them shows the waste.  A multiply-add is two
operations.  Sizes come from the config file's ``model`` entry.
"""
