"""Per resume, the part of ``Trainer.restore`` outside ``engine.load``
(the host-to-device copy of every leaf) plus the first train step, s."""


def read(ctx):
    r = ctx["records"]
    if not r.get("resumes"):
        return None
    return (r["restore_seconds"] - r["load_seconds"]
            + r["first_step_seconds"]) / r["resumes"]
