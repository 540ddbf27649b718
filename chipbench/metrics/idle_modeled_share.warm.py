"""The share of the device's idle seconds in the traced window during
which the program's innermost active spans were modeled waits
(``nexus.wait``) and none did real work, in percent."""
from chipbench import spans


def read(run):
    return spans.idle_modeled_share(run)
