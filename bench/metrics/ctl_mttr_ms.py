"""Control plane: the controller's own MTTR of the failover (RecoveryRecord.mttr)."""


def read(run):
    rec = run["recovery"]
    if rec is None or not rec["recovered"]:
        return None
    return 1e3 * rec["mttr_s"]
