"""A closed backlog (offline batch): ``waiting_per_slot`` requests wait for
each slot, and each completion queues the next."""
OPEN = False


def waiting(params: dict, slots: int) -> int:
    return int(params["waiting_per_slot"]) * slots
