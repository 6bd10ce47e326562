select count(*) cnt
from store_sales, household_demographics, time_dim, store
where ss_sold_time_sk = t_time_sk
  and ss_hdemo_sk = hd_demo_sk and ss_store_sk = s_store_sk
  and t_hour = 20 and t_minute >= 30 and hd_dep_count = 7
  and s_store_name = 'ese'
order by cnt
limit 100
