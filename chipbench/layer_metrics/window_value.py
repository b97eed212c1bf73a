"""Reader: a number the driver put beside its window's record
(``ctx['window'][key]``), as it is; nothing where the driver put none."""


def read(ctx, key):
  return ctx['window'].get(key)
