"""Names that the generator and the plain reference both give one thing.

Imports nothing, so that the reference (which imports nothing of the
program) and the generator (which drives it) share the names without
either importing the other."""


def update_key(job_id: str, version: int) -> str:
    """The name of one update of a job: the job and its new version."""
    return f"{job_id}@v{int(version)}"
