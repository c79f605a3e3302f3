"""model layer: op-seconds no ``sec_*`` scope names over all op-seconds of the
traced steps: the coverage receipt of every ``step_*_ms`` entry."""
from benchmarks import step_sections


def read(run):
    return step_sections.unattributed_share(run)
